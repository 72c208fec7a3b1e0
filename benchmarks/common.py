"""Shared benchmark helpers."""
import contextlib
import time

import jax

from repro import obs
from repro.launch.compile_cache import use_compile_cache


def time_call(fn, *args, warmup=2, iters=5):
    """Median wall time of fn(*args) in seconds (block_until_ready)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def emit(name, us_per_call, derived):
    print(f"{name},{us_per_call:.1f},{derived}")


def verify_plan_timed(plan, rows=None, cols=None, vals=None,
                      mode: str = "fast") -> float:
    """Run the stream verifier on a freshly built plan; return seconds.

    Every benchmark that encodes a plan funnels its ingest check through
    here, so a sweep can't publish numbers for a stream that violates the
    format contract.  Raises :class:`repro.analysis.VerificationError`
    on any finding; pass the source COO (with ``mode="full"``) to also
    prove the round-trip.
    """
    from repro.analysis.verify import VerificationError, verify_plan
    t0 = time.perf_counter()
    if rows is not None and mode == "full":
        diags = verify_plan(plan, rows, cols, vals, mode="full")
    else:
        diags = verify_plan(plan, mode=mode)
    dt = time.perf_counter() - t0
    if not diags.ok:
        raise VerificationError(diags)
    return dt


def add_trace_arg(ap):
    """Attach the standard ``--trace-out`` flag to an argparse parser."""
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of this run to "
                         "PATH (load in ui.perfetto.dev)")
    return ap


@contextlib.contextmanager
def tracing(path):
    """Trace the enclosed block to ``path`` (no-op when path is falsy).

    Enables the global tracer for the block, then writes + schema-checks
    the Chrome trace JSON — every ``--trace-out`` benchmark funnels
    through here so they all emit the same validated format.
    """
    if not path:
        yield
        return
    obs.clear()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.write_chrome_trace(path)
        print(f"# trace written to {path} "
              f"({obs.TRACER.event_count()} events)")


def run_main(run, argv=None, header: bool = False):
    """Standard bare-``main`` wrapper: ``--trace-out`` (and ``--dry-run``
    when the entry point takes one).

    ``run`` is the benchmark's entry point; ``--dry-run`` is only offered
    when its signature accepts a ``dry_run`` keyword, so the fixed-size
    table/figure benchmarks get the trace flag without a lying option.
    """
    import argparse
    import inspect
    takes_dry = "dry_run" in inspect.signature(run).parameters
    ap = argparse.ArgumentParser()
    if takes_dry:
        ap.add_argument("--dry-run", action="store_true",
                        help="shrink the workload (CI smoke)")
    add_trace_arg(ap)
    args = ap.parse_args(argv)
    use_compile_cache()
    if header:
        print("name,us_per_call,derived")
    with tracing(args.trace_out):
        run(dry_run=args.dry_run) if takes_dry else run()
