"""Record the small profiler trace that ``test_bench.py`` reduces.

Serves a small seeded matrix through ``SpMVService`` on the chip with the
harness's host annotations (``bench.sync``, ``bench.window``, ``bench.submit``,
``bench.result_wait``, ``bench.sleep``), then one small PageRank solve
(``bench.solve``: a ``while`` op that holds the stream ops, and a
compile inside the window), and keeps the ``.xplane.pb``:

    python3 bench/tests/record_trace.py <out_dir>

Needs a TPU; exits non-zero without one.  Prints each plane's lines and
their first event names, so the reduction can be checked by eye.
"""
from __future__ import annotations

import glob
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    out = Path(argv[1])
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: no TPU", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData, TraceAnnotation
    from repro.core.registry import MatrixRegistry
    from repro.serve.spmv_service import SpMVService

    rng = np.random.default_rng(0)
    n, nnz = 4096, 40_000
    rows = rng.integers(0, n, nnz)
    cols = rng.integers(0, n, nnz)
    vals = rng.standard_normal(nnz).astype(np.float32)
    reg = MatrixRegistry(verify="fast")
    mid = reg.put(rows, cols, vals, (n, n))
    svc = SpMVService(reg, max_bucket=4)
    xs = rng.standard_normal((4, n)).astype(np.float32)
    for w in (1, 2, 4):                           # warm every width
        ts = [svc.submit(mid, xs[j]) for j in range(w)]
        svc.flush()
        for t in ts:
            svc.result(t, timeout=0)
    pr = reg.put(rows, cols, np.abs(vals) / np.bincount(
        cols, np.abs(vals), minlength=n)[cols], (n, n))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = out.with_name(out.name + "_raw")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    t_sync = time.perf_counter_ns()
    with TraceAnnotation("bench.sync"):
        pass
    with TraceAnnotation("bench.window"):
        with svc:
            for w in (1, 4, 2):
                with TraceAnnotation("bench.submit"):
                    ts = [svc.submit(mid, xs[j]) for j in range(w)]
                with TraceAnnotation("bench.result_wait"):
                    for t in ts:
                        svc.result(t, timeout=120)
                with TraceAnnotation("bench.sleep"):
                    time.sleep(0.02)
        with TraceAnnotation("bench.solve"):
            svc.solve(pr, "pagerank", tol=1e-6)
    jax.profiler.stop_trace()
    reg.close()
    pb = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)[0]
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(pb, out / "trace.xplane.pb")
    print(f"sync perf_counter_ns {t_sync}; kept {out / 'trace.xplane.pb'}")
    for plane in ProfileData.from_file(pb).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs),
                  [(e.name[:60], e.start_ns, e.duration_ns) for e in evs[:6]])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
