"""Kernel-profiling hooks: jax.profiler integration + per-plan cost model.

The paper's results are roofline points — achieved GB/s of A-stream
traffic against the HBM peak — so a benchmark sweep wants, per run, the
plan's counted cost (stream bytes, slots, padding) next to its
*measured* wall-time.  :func:`plan_cost_report` produces exactly that for
any :class:`~repro.core.spmv.SerpensOperator` (surfaced as
``op.cost_report()``), and :func:`profiler_trace` wraps a block in a
``jax.profiler`` trace for TensorBoard/Perfetto-level kernel detail when
available.

jax is imported lazily so this module stays importable from numpy-only
worker processes.
"""
from __future__ import annotations

import contextlib
import time
import warnings


def plan_cost_report(op, *, measure: bool = False,
                     backend: str | None = None, iters: int = 3) -> dict:
    """Cost report for one operator's channel-shard plan.

    Per shard: nnz, slots, stream bytes, padding ratio and per-lane
    live-slot imbalance (max/mean) — all counted from the plan.  With
    ``measure=True`` one matvec is compiled + timed (median of ``iters``)
    and the report adds the device kind, its HBM peak from
    :data:`repro.core.scheduler.DEVICE_PEAKS`, the achieved GB/s and its
    fraction of that peak — the roofline position — plus per-shard
    measured time attributed proportionally to stream bytes (shards
    dispatch in one call, so only the total is directly observable).
    A device with no row in the peak table (the CPU among them) raises
    ``KeyError`` before anything is timed.
    """
    import numpy as np
    from repro.core.format import SENTINEL
    plan = op.plan
    shards = []
    for i, sm in enumerate(plan.shards):
        # Per-lane live-slot imbalance (max/mean): the structural feature
        # the auto-tuner keys on — 1.0 is perfectly balanced lanes, higher
        # means some lanes pad while others stream.
        live = (np.asarray(sm.idx) != SENTINEL).sum(axis=(0, 1))
        lane_mean = float(live.mean()) if live.size else 0.0
        imb = float(live.max() / lane_mean) if lane_mean > 0.0 else 1.0
        shards.append({
            "shard": i,
            "nnz": int(sm.nnz),
            "n_aux": int(sm.n_aux),
            "slots": int(sm.idx.size),
            "stream_bytes": int(sm.stream_bytes),
            "padding_ratio": float(sm.padding_ratio),
            "lane_slot_imbalance": imb,
        })
    total_bytes = int(plan.stream_bytes)
    report = {
        "shape": [int(s) for s in op.shape],
        "nnz": int(plan.nnz),
        "partition": plan.spec.partition,
        "num_shards": int(plan.num_shards),
        # Slot width follows the plan's value dtype: 4 B packed index +
        # 4 B fp32 value (the paper's 8 B element) or + 2 B bf16 value.
        "value_dtype": plan.config.value_dtype,
        "bytes_per_slot": 4 + plan.config.value_bytes,
        "stream_bytes": total_bytes,
        "bytes_per_nnz": total_bytes / max(int(plan.nnz), 1),
        "padded_slots": int(plan.idx.size),
        "padding_ratio": float(plan.padding_ratio),
        "lane_assign": plan.spec.lane_assign,
        "lane_slot_imbalance": max(
            (sh["lane_slot_imbalance"] for sh in shards), default=1.0),
        "shards": shards,
    }
    if measure:
        import jax
        from repro.core.scheduler import device_peaks
        kind = jax.devices()[0].device_kind
        peak_gbps = device_peaks(kind).hbm_bytes_per_s / 1e9
        x = np.random.default_rng(0).normal(
            size=op.shape[1]).astype(np.float32)
        jax.block_until_ready(op.matvec(x, backend=backend))  # compile
        times = []
        for _ in range(max(1, iters)):
            t0 = time.perf_counter()
            jax.block_until_ready(op.matvec(x, backend=backend))
            times.append(time.perf_counter() - t0)
        times.sort()
        measured = times[len(times) // 2]
        report["device_kind"] = kind
        report["peak_hbm_gbps"] = peak_gbps
        report["measured_matvec_s"] = measured
        report["achieved_gbps"] = total_bytes / measured / 1e9
        report["roofline_fraction"] = report["achieved_gbps"] / peak_gbps
        for sh in shards:
            frac = sh["stream_bytes"] / max(total_bytes, 1)
            sh["measured_s_attributed"] = measured * frac
    return report


@contextlib.contextmanager
def profiler_trace(logdir: str | None):
    """``jax.profiler`` trace around a block (TensorBoard/Perfetto logs).

    No-op when ``logdir`` is falsy; degrades to a warning + no-op when
    the profiler is unavailable (e.g. a build without profiling support),
    so benchmark flags can pass it through unconditionally.
    """
    if not logdir:
        yield
        return
    try:
        import jax
        jax.profiler.start_trace(str(logdir))
    except Exception as e:                      # noqa: BLE001 — degrade
        warnings.warn(f"jax profiler unavailable ({e}); continuing "
                      f"without a device trace", stacklevel=2)
        yield
        return
    try:
        yield
    finally:
        jax.profiler.stop_trace()
