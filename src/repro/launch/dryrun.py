import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent on the
production mesh — 16×16 (single pod, 256 chips) and 2×16×16 (two pods,
512 chips) — using ShapeDtypeStruct stand-ins (no real allocation), and
extracts the roofline raw terms:

  * ``memory_analysis()``  → bytes per device (does the cell fit 16 GB?)
  * ``cost_analysis()``    → HLO FLOPs + HBM bytes accessed
  * HLO-text collective scan → per-chip collective traffic estimate

Results are cached as JSON under results/dryrun/ (one file per cell) so the
sweep is restartable; benchmarks/roofline.py consumes them.

Usage:
  python -m repro.launch.dryrun --arch chatglm3-6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, valid_cells
from repro.launch import hlo_analysis
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh, dp_axes
from repro.launch import sharding as sh
from repro.models import layers as L
from repro.models.model import build
from repro.train import optimizer as opt_lib
from repro.train.trainer import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../results/dryrun")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(txt: str) -> int:
    m = _SHAPE_RE.match(txt)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_OP_RE = re.compile(
    r"= (?P<out>.*?) (?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>[\w\-.]*)\((?P<operands>.*?)\)",)


def collective_bytes(hlo_text: str) -> dict:
    """Per-chip collective traffic estimate from (SPMD-partitioned) HLO.

    Ring-algorithm accounting: all-reduce ≈ 2× payload per chip,
    all-gather/all-to-all/permute ≈ output payload, reduce-scatter ≈ input
    payload.  Shapes in partitioned HLO are already per-device.  *-start/
    *-done async pairs are counted once (on the -start op).
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if "done" in m.group("suffix"):
            continue  # async completion — counted at -start
        op = m.group("op")
        out_bytes = sum(_shape_bytes(s.group(0))
                        for s in _SHAPE_RE.finditer(m.group("out")))
        in_bytes = sum(_shape_bytes(s.group(0))
                       for s in _SHAPE_RE.finditer(m.group("operands")))
        if op == "all-reduce":
            nbytes = 2 * out_bytes
        elif op == "reduce-scatter":
            nbytes = in_bytes
        else:
            nbytes = out_bytes
        out[op] += nbytes
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, weak-type-correct, shardable)
# ---------------------------------------------------------------------------
def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg, shape_name):
    """Batch ShapeDtypeStructs for one assigned shape."""
    seq, gbatch, kind = SHAPES[shape_name]
    if kind == "train":
        batch = {"inputs": sds((gbatch, seq), jnp.int32),
                 "labels": sds((gbatch, seq), jnp.int32)}
    elif kind == "prefill":
        batch = {"inputs": sds((gbatch, seq), jnp.int32)}
    else:  # decode: one new token against a cache of length `seq`
        batch = {"tokens": sds((gbatch, 1), jnp.int32)}
    if cfg.vision_tokens and kind != "decode":
        batch["patches"] = sds(
            (gbatch, cfg.vision_tokens, cfg.vision_embed_dim), jnp.float32)
    if cfg.encoder_layers and kind != "decode":
        batch["frames"] = sds((gbatch, cfg.encoder_seq, cfg.d_model),
                              jnp.float32)
    return batch


def moment_dtype_for(cfg) -> str:
    """bf16 Adam moments for ≥50B-param archs (DESIGN.md §6)."""
    return "bfloat16" if cfg.approx_params() >= 50e9 else "float32"


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               kv_quant: bool = False):
    import dataclasses
    cfg = get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_cache_quant=True)
    seq, gbatch, kind = SHAPES[shape_name]
    lm = build(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = dp_axes(mesh)
    rng = jax.random.key(0)

    params_sds = jax.eval_shape(lm.init, rng)
    pspecs = sh.param_specs(params_sds)
    pshard = sh.to_shardings(mesh, pspecs)
    bspecs = sh.to_shardings(mesh, {
        k: P(dp) for k in input_specs(cfg, shape_name)})

    shard_seq = (kind == "decode" and gbatch < mesh.devices.size
                 and shape_name == "long_500k")
    with L.mesh_context(mesh, dp_axes=dp, seq_shard_kv=shard_seq), mesh:
        if kind == "train":
            ocfg = opt_lib.OptimizerConfig(
                moment_dtype=moment_dtype_for(cfg))
            opt_sds = jax.eval_shape(
                lambda p: opt_lib.init(ocfg, p), params_sds)
            ospecs = {"m": pspecs, "v": pspecs, "step": P()}
            oshard = sh.to_shardings(mesh, ospecs)
            step_fn = make_train_step(lm, ocfg)
            batch = input_specs(cfg, shape_name)
            lowered = jax.jit(
                step_fn,
                in_shardings=(pshard, oshard, bspecs),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1),
            ).lower(params_sds, opt_sds, batch)
        elif kind == "prefill":
            batch = input_specs(cfg, shape_name)
            lowered = jax.jit(
                lambda p, b: lm.prefill(p, b, seq + 1),
                in_shardings=(pshard, bspecs),
            ).lower(params_sds, batch)
        else:  # decode
            cache_sds = jax.eval_shape(
                lambda: lm.init_cache(gbatch, seq))
            cspecs = sh.cache_specs(cfg, cache_sds, mesh,
                                    shard_seq=shard_seq)
            cshard = sh.to_shardings(mesh, cspecs)
            tok = sds((gbatch, 1), jnp.int32)
            tokshard = sh.to_shardings(mesh, P(dp) if gbatch > 1 else P())
            lowered = jax.jit(
                lm.decode_step,
                in_shardings=(pshard, cshard, tokshard, None),
                out_shardings=(None, cshard),
                donate_argnums=(1,),
            ).lower(params_sds, cache_sds, tok, sds((), jnp.int32))
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        coll = collective_bytes(hlo_text)
        scan_aware = hlo_analysis.analyze(hlo_text)
        scan_aware.pop("while_trips", None)
        if os.environ.get("REPRO_DUMP_HLO"):
            os.makedirs(RESULTS_DIR, exist_ok=True)
            dump = cell_path(arch, shape_name, multi_pod).replace(
                ".json", ".hlo.txt")
            with open(dump, "w") as f:
                f.write(hlo_text)
    n_params = cfg.approx_params()
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": kind, "seq": seq, "global_batch": gbatch,
        "chips": int(mesh.devices.size),
        "compile_seconds": round(compile_s, 1),
        "flops_per_device": float(cost.get("flops", -1)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", -1)),
        "collectives": coll,
        "scan_aware": scan_aware,   # trip-count-corrected (hlo_analysis.py)
        "params": int(n_params),
        "active_params": int(cfg.active_params()),
        "moment_dtype": moment_dtype_for(cfg) if kind == "train" else None,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
    }
    return record


def cell_path(arch, shape, multi_pod):
    mesh = "2x16x16" if multi_pod else "16x16"
    safe = arch.replace("/", "_")
    return os.path.join(RESULTS_DIR, f"{safe}__{shape}__{mesh}.json")


def run_cell(arch, shape, multi_pod, force=False, kv_quant=False):
    path = cell_path(arch, shape, multi_pod)
    if kv_quant:
        path = path.replace(".json", "__kvq.json")
    if not force and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    try:
        rec = lower_cell(arch, shape, multi_pod, kv_quant=kv_quant)
        rec["status"] = "ok"
    except Exception as e:  # record failures for triage
        rec = {"arch": arch, "shape": shape,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (§Perf B3) for decode cells")
    args = ap.parse_args()
    use_compile_cache()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells = (valid_cells() if args.all
             else [(args.arch, args.shape)])
    for arch, shape in cells:
        for mp in meshes:
            t0 = time.time()
            rec = run_cell(arch, shape, mp, force=args.force,
                           kv_quant=args.kv_quant)
            status = rec.get("status")
            extra = ("" if status == "ok"
                     else " :: " + rec.get("error", "")[:120])
            print(f"[{time.strftime('%H:%M:%S')}] {arch:28s} {shape:12s} "
                  f"{'2x16x16' if mp else '16x16':8s} {status:5s} "
                  f"({time.time()-t0:5.1f}s){extra}", flush=True)


if __name__ == "__main__":
    main()
