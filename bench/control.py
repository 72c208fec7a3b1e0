"""The correctness control: the program's own bfloat16 value path.

    python3 bench/control.py --workload pokec.pagerank --seeds 11,12,13 \
        --seconds 15

Runs the cell through ``run.run_cell`` with the configuration's
``value_dtype`` set to ``bfloat16``, the nearest precision below the
float32 that the configurations state, once per seed, and prints each
compared number beside its limit.  Every limit has to lie below the
smallest such reading (``PERF.md`` gives the readings).  The benchmark's
own runs never call it; a window shorter than the cell's compares fewer
answers and so reads lower, which errs on the safe side.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(bench_run.ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 1
    bench = bench_run.load_benchmark()
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    config = bench_run.load_json("configs", cell["config"])
    config["value_dtype"] = "bfloat16"
    for seed in (int(s) for s in args.seeds.split(",")):
        res = bench_run.run_cell(bench, args.workload, seed=seed,
                                 seconds=args.seconds, trace=False,
                                 config=config, log=lambda s: None)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "value_dtype": "bfloat16",
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
