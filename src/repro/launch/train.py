"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On real hardware, run one process per host under your cluster scheduler;
jax.distributed picks up the pod topology and `make_production_mesh()`
builds the (pod, data, model) mesh.  On this container, ``--reduced`` runs
the same code path end-to-end on CPU with the smoke-size config, and
``--host-devices N`` simulates an N-device mesh.
"""
import argparse
import dataclasses
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size config (CPU-trainable)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="simulate N host devices (data×model mesh)")
    ap.add_argument("--data-axis", type=int, default=0)
    ap.add_argument("--model-axis", type=int, default=1)
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    # imports after XLA_FLAGS
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs import get_config, reduced_config
    from repro.data.pipeline import SyntheticLM, add_modality_stubs
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import build
    from repro.train.optimizer import OptimizerConfig
    from repro.train.trainer import Trainer, TrainConfig

    cfg = reduced_config(args.arch) if args.reduced else get_config(
        args.arch)
    lm = build(cfg)
    print(f"arch {cfg.arch_id}: ~{cfg.approx_params()/1e6:.1f}M params "
          f"({cfg.active_params()/1e6:.1f}M active)")

    mesh = None
    if args.host_devices:
        d = args.data_axis or args.host_devices
        m = args.model_axis or 1
        mesh = make_host_mesh(d, m)
        print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    data = SyntheticLM(cfg.vocab_size, args.seq, args.global_batch, seed=0)

    def batch_fn(step):
        return add_modality_stubs(data.batch_at(step), cfg, step)

    tc = TrainConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=50,
        log_every=10,
        opt=OptimizerConfig(lr=args.lr, warmup_steps=min(20, args.steps),
                            total_steps=args.steps))
    tr = Trainer(lm, batch_fn, tc, mesh=mesh)
    if tr.step:
        print(f"resumed at step {tr.step}")
    hist = tr.run()
    for h in hist:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"lr {h['lr']:.2e}  gnorm {h['grad_norm']:.2f}")


if __name__ == "__main__":
    main()
