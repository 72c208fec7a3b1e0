"""Open-loop rate sweep: the highest rate a cell's service sustains.

    python3 bench/sweep.py --workload pokec.serve_open --seed 5 \
        --seconds 40 --rates 1.0,1.5,2.0,2.5,3.0

One set-up, then one open-loop window per rate, lowest first.  A rate is
sustained when every request completed and the wait did not grow
through the window: latency against due time has a slope under
``MAX_GROWTH``.  Run once on the chip; the cell's traffic file keeps 0.8 of
the highest sustained rate as data.  The benchmark's runs never call it.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as bench_run

MAX_GROWTH = 0.1


def backlog_growth(due: list[float], lat: list[float]) -> float:
    """Least-squares slope of latency against due time: seconds of extra
    wait per second of window.  A queue that keeps up holds it near 0;
    one that falls behind grows it towards ``rate / capacity - 1``."""
    return float(np.polyfit(np.asarray(due) - due[0], lat, 1)[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(bench_run.ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU", file=sys.stderr)
        return 1
    import loadgen
    from repro.core.registry import MatrixRegistry
    from repro.serve.spmv_service import SpMVService

    bench = bench_run.load_benchmark()
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    config = bench_run.load_json("configs", cell["config"])
    mix = bench_run.load_json("traffic", cell["traffic"])
    gen = bench_run.load_reader("generators", config["matrix"]["generator"])
    rows, cols, vals, shape = gen.generate(config["matrix"], args.seed)
    reg = MatrixRegistry(byte_budget=int(config["registry"]["byte_budget"]),
                         verify=config["registry"]["verify"])
    mid = reg.put(rows, cols, vals, shape, value_dtype=config["value_dtype"])
    svc = SpMVService(reg, max_bucket=int(config["service"]["max_bucket"]))
    pool = bench_run._pool(mix, shape[1], args.seed)
    bench_run.warm_up(svc, mid, mix, pool)
    points = []
    with svc:
        for rate in (float(r) for r in args.rates.split(",")):
            stats0 = svc.stats
            win = loadgen.run(svc, mid, dict(mix, rate=rate), args.seconds,
                              pool, args.seed)
            stats1 = svc.stats
            reqs = sorted(win.requests, key=lambda r: r.due)
            ok = [r for r in reqs if r.error is None]
            lat = [r.done - r.due for r in ok]
            growth = backlog_growth([r.due for r in ok], lat)
            point = {
                "rate": rate, "requests": len(reqs),
                "failed": sum(r.error is not None for r in reqs),
                "completed_per_s": len(lat) / (win.end - win.start),
                "p50_ms": loadgen.percentile([x * 1e3 for x in lat], 50),
                "p80_ms": loadgen.percentile([x * 1e3 for x in lat], 80),
                "p90_ms": loadgen.percentile([x * 1e3 for x in lat], 90),
                "batch_width": ((stats1.vectors - stats0.vectors)
                                / max(1, stats1.batches - stats0.batches)),
                "backlog_growth": growth,
                "sustained": len(lat) == len(reqs) and growth < MAX_GROWTH}
            points.append(point)
            print(json.dumps(point), flush=True)
    reg.close()
    ok = [p["rate"] for p in points if p["sustained"]]
    print(json.dumps({"points": points,
                      "highest_sustained": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
