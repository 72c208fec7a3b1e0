"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Prefill + batched greedy decode with the ServeEngine.  ``--reduced`` runs
the smoke config on CPU; ``--shard-kv-seq`` exercises the long-context
sequence-sharded decode path on a simulated mesh.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--shard-kv-seq", action="store_true")
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    import numpy as np
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs import get_config, reduced_config
    from repro.data.pipeline import add_modality_stubs
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import build
    from repro.serve.engine import ServeEngine

    cfg = reduced_config(args.arch) if args.reduced else get_config(
        args.arch)
    lm = build(cfg)
    params = jax.jit(lm.init)(jax.random.key(0))

    mesh = None
    if args.host_devices:
        mesh = make_host_mesh(args.host_devices, 1)

    max_len = cfg.vision_tokens + args.prompt_len + args.gen + 8
    eng = ServeEngine(lm, params, max_len=max_len, mesh=mesh,
                      shard_kv_seq=args.shard_kv_seq)

    rng = np.random.default_rng(0)
    batch = {"inputs": np.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        np.int32)}
    batch = add_modality_stubs(batch, cfg)
    out = eng.generate(batch, steps=args.gen,
                       temperature=args.temperature)
    print(f"arch {cfg.arch_id}: generated {out.shape} tokens")
    for i, row in enumerate(np.asarray(out)):
        print(f"  req {i}: {row.tolist()}")


if __name__ == "__main__":
    main()
