"""Compile spans and the per-plan cost model.

:func:`install_compile_spans` turns JAX's compile events into ``obs``
spans, so a trace shows where a call traced, lowered, compiled or loaded
a program from the persistent cache; :func:`plan_cost_report` counts a
:class:`~repro.core.spmv.SerpensOperator`'s plan (surfaced as
``op.cost_report()``): stream bytes, slots, padding.

jax is imported lazily so this module stays importable from numpy-only
worker processes.
"""
from __future__ import annotations

import threading

from repro.obs.trace import TRACER

# jax.monitoring duration events -> span names.  The backend compile
# covers a load from the persistent cache, which nests inside it.
COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax-trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax-lower",
    "/jax/core/compile/backend_compile_duration": "jax-compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax-cache-load",
}

_install_lock = threading.Lock()
_installed = False


def _hear_compile(event: str, duration: float, **kw) -> None:
    """Record one compile event as a span ending now, on the compiling
    thread; JAX names the function for all but the cache load."""
    if not TRACER.enabled:
        return
    name = COMPILE_SPANS.get(event)
    if name is None:
        return
    if "fun_name" in kw:
        TRACER.event(name, duration, cat="jax", fun=kw["fun_name"])
    else:
        TRACER.event(name, duration, cat="jax")


def install_compile_spans() -> None:
    """Register the compile listener with ``jax.monitoring`` once per
    process; it records spans only while tracing is enabled."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_hear_compile)
        _installed = True


def plan_cost_report(op) -> dict:
    """Cost report for one operator's channel-shard plan.

    Per shard: nnz, slots, stream bytes, padding ratio and per-lane
    live-slot imbalance (max/mean) — all counted from the plan.
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
    return report
