"""Instrumentation overhead: tracing-off cost must stay under 3%.

    PYTHONPATH=src:. python benchmarks/obs_overhead.py [--dry-run]

The observability subsystem rides the serving hot path (every submit /
flush / dispatch / collect crosses span guards, flow emits and histogram
observes), so this benchmark holds its budget explicitly:

1. **Disabled path (asserted)** — microbenchmark the per-call cost of a
   disabled span / instant / flow and a histogram observe, multiply by a
   deliberately generous per-request call count, and compare to the
   measured per-request serving time.  The ratio must stay **< 3%**.
   Asserting the analytic product rather than the difference of two
   end-to-end runs is a 1-core-CI decision: wall-clock deltas between two
   sweep runs on a shared core are noisier than the 3% being asserted,
   while the per-call guard cost (~tens of ns) measures cleanly over 10^6
   calls.
2. **Enabled path (recorded)** — the same serving burst with tracing on,
   reported as a ratio next to the off numbers so regressions are visible
   in the sweep JSON; not asserted (buffering events costs real work and
   CI noise owns that delta).

Emits the standard CSV rows plus a JSON report (``--out``).
"""
import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import emit
from repro import obs
from repro.core import format as F
from repro.core.registry import MatrixRegistry
from repro.data import matrices as M
from repro.serve.spmv_service import SpMVService

DEFAULT_OUT = os.path.join("results", "obs_overhead.json")
OVERHEAD_BUDGET = 0.03

# Instrumentation calls one served request crosses, by primitive.
# Per-request: submit + result-collect spans, 3 flow emits, 1 dispatch
# latency observe.  Per-batch (amortized over B coalesced requests):
# flush/coalesce/dispatch/pack/compute/device-block spans, the guards of
# the queue-wait and inflight spans (counted as one more span), the flush
# + batch-size observes, and 3 counter adds.
PER_REQUEST = {"span": 2, "flow": 3, "observe": 1}
PER_BATCH = {"span": 7, "observe": 2, "counter": 3}


def _per_call_ns(fn, iters: int) -> float:
    t0 = time.perf_counter_ns()
    for _ in range(iters):
        fn()
    return (time.perf_counter_ns() - t0) / iters


def measure_guard_costs(iters: int) -> dict:
    """Per-call ns of each disabled-path primitive (tracing OFF)."""
    assert not obs.is_enabled()
    hist = obs.MetricsRegistry().histogram("bench_hist")
    counter = obs.MetricsRegistry().counter("bench_counter")

    def span_call():
        with obs.span("x", a=1):
            pass

    costs = {
        "span": _per_call_ns(span_call, iters),
        "instant": _per_call_ns(lambda: obs.instant("x", a=1), iters),
        "flow": _per_call_ns(lambda: obs.flow_step("x", 1), iters),
        "observe": _per_call_ns(lambda: hist.observe(0.001), iters),
        "counter": _per_call_ns(lambda: counter.inc(), iters),
    }
    return costs


def overhead_per_request_s(costs: dict, batch_size: float) -> float:
    """Modeled instrumentation seconds per served request: the per-request
    primitives plus the per-batch ones amortized over the measured mean
    batch size."""
    b = max(1.0, batch_size)
    ns = sum(n * costs[k] for k, n in PER_REQUEST.items())
    ns += sum(n * costs[k] for k, n in PER_BATCH.items()) / b
    return ns / 1e9


def serve_burst(svc, mid, xs) -> float:
    """Seconds per request over one submitted+flushed+collected burst."""
    t0 = time.perf_counter()
    tickets = [svc.submit(mid, x) for x in xs]
    svc.flush()
    for t in tickets:
        svc.result(t, timeout=30.0)
    return (time.perf_counter() - t0) / len(tickets)


def run(dry_run: bool = False, out_path: str | None = DEFAULT_OUT) -> dict:
    n = 2_000 if dry_run else 20_000
    nnz = 20_000 if dry_run else 200_000
    burst = 16 if dry_run else 64
    guard_iters = 200_000 if dry_run else 1_000_000
    cfg = (F.SerpensConfig(segment_width=512, lanes=16, sublanes=8)
           if dry_run else F.SerpensConfig(segment_width=8192, lanes=128))

    obs.disable()
    costs = measure_guard_costs(guard_iters)
    emit("obs_overhead/guard", max(costs.values()) / 1e3,
         f"span_ns={costs['span']:.0f};observe_ns={costs['observe']:.0f};"
         f"counter_ns={costs['counter']:.0f}")

    rows, cols, vals = M.power_law_graph(n, nnz, seed=7)
    reg = MatrixRegistry(config=cfg, backend="xla")
    mid = reg.put(rows, cols, vals, (n, n))
    rng = np.random.default_rng(1)
    xs = rng.normal(size=(burst, n)).astype(np.float32)
    svc = SpMVService(reg, max_bucket=16, backend="xla")

    serve_burst(svc, mid, xs)                  # compile warmup
    per_req_off = min(serve_burst(svc, mid, xs) for _ in range(3))
    mean_batch = svc.stats.mean_batch_size
    emit("obs_overhead/request_off", per_req_off * 1e6,
         f"burst={burst};mean_batch={mean_batch:.1f}")

    # The asserted bound: the measured per-primitive cost times the call
    # profile a served request actually crosses, at the measured batch
    # size (batch-level calls amortize over B coalesced requests).
    overhead_s = overhead_per_request_s(costs, mean_batch)
    ratio_off = overhead_s / per_req_off
    emit("obs_overhead/ratio_off", 0.0,
         f"ratio={ratio_off:.5f};budget={OVERHEAD_BUDGET}")
    assert ratio_off < OVERHEAD_BUDGET, (
        f"disabled-path instrumentation costs {ratio_off:.2%} of a served "
        f"request ({overhead_s*1e6:.1f} us modeled vs "
        f"{per_req_off*1e6:.0f} us measured) — budget is "
        f"{OVERHEAD_BUDGET:.0%}")

    # Recorded (not asserted): the same burst with tracing buffering.
    obs.clear()
    obs.enable()
    per_req_on = min(serve_burst(svc, mid, xs) for _ in range(3))
    obs.disable()
    ratio_on = (per_req_on - per_req_off) / per_req_off
    emit("obs_overhead/request_on", per_req_on * 1e6,
         f"tracing_on_delta={ratio_on:+.2%}")

    result = {
        "guard_costs_ns": costs,
        "call_profile": {"per_request": PER_REQUEST,
                         "per_batch": PER_BATCH},
        "mean_batch_size": mean_batch,
        "per_request_off_s": per_req_off,
        "per_request_on_s": per_req_on,
        "ratio_off": ratio_off,
        "ratio_on_delta": ratio_on,
        "budget": OVERHEAD_BUDGET,
        "burst": burst,
        "dry_run": dry_run,
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
        emit("obs_overhead/json", 0.0, f"path={out_path}")
    reg.close()
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="small matrix + burst (CI smoke)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="where to write the report JSON")
    # No --trace-out here: this benchmark toggles the global tracer
    # itself (off for the asserted phase, on for the recorded one).
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(dry_run=args.dry_run, out_path=args.out)
