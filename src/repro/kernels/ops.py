"""Jit'd wrappers around the Serpens kernels + the XLA stream executor.

Three execution paths, selectable via ``backend=``:

  * ``"xla"``       — the Serpens stream processed as one vectorized
                      gather/scatter in plain XLA.  It compiles for the
                      TPU (v5e) at published matrix sizes and is the
                      default on every platform.
  * ``"pallas"``    — the hand kernel (``serpens_spmv.py``).  On a TPU it
                      is compiled for real, never interpreted; Mosaic does
                      not lower its gather/scatter yet, so there it raises
                      the compiler's error.  Off the TPU it runs in the
                      Pallas interpreter (the CPU tests).
  * ``"auto"``      — ``"xla"``, until a Pallas kernel compiles for the
                      chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.format import ROW_BITS, COL_MASK, SerpensMatrix
from repro.kernels import serpens_spmv
from repro.obs import profile as obs_profile

# JAX's compile events become ``jax-*`` spans while tracing is on.
obs_profile.install_compile_spans()

# Trace-time dispatch counter: bumped once per run_stream/run_stream_fused
# *call* (i.e. per stream pass emitted into a trace, not per executed
# iteration — inside a lax.while_loop body it counts passes per body
# trace).  Solvers use the delta across a body trace to verify the fused
# path really issues ONE stream pass per iteration.
_trace_dispatches = 0


def trace_dispatch_count() -> int:
    """Total run_stream/run_stream_fused dispatches emitted so far."""
    return _trace_dispatches


def _count_dispatch() -> None:
    global _trace_dispatches
    _trace_dispatches += 1


def _decode(idx, seg_ids_tile, segment_width, lanes):
    """Decode the packed stream: global rows/cols + live mask."""
    live = idx != -1
    rows_local = jnp.where(live, (idx >> ROW_BITS) & COL_MASK, 0)
    cols_local = jnp.where(live, idx & COL_MASK, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 2)
    rows = rows_local * lanes + lane
    cols = seg_ids_tile[:, None, None] * segment_width + cols_local
    return live, rows, cols


@functools.partial(jax.jit, static_argnames=("num_rows_padded",
                                             "segment_width"))
def spmv_stream_xla(idx, val, seg_ids_tile, x_flat, *, num_rows_padded,
                    segment_width):
    """Vectorized XLA execution of the Serpens stream (single scatter-add)."""
    lanes = idx.shape[2]
    live, rows, cols = _decode(idx, seg_ids_tile, segment_width, lanes)
    xv = x_flat[cols.reshape(-1)].reshape(cols.shape)
    # bf16-load / fp32-accumulate: the upcast is exact, the MAC stays f32.
    contrib = jnp.where(live, val.astype(jnp.float32) * xv, 0.0)
    acc = jnp.zeros((num_rows_padded,), jnp.float32)
    return acc.at[rows.reshape(-1)].add(contrib.reshape(-1))


@functools.partial(jax.jit, static_argnames=("num_rows_padded",
                                             "segment_width"))
def spmm_stream_xla(idx, val, seg_ids_tile, x_mat, *, num_rows_padded,
                    segment_width):
    """Multi-vector stream execution: x_mat is (K_padded, N) → (R_padded, N)."""
    lanes = idx.shape[2]
    n = x_mat.shape[1]
    live, rows, cols = _decode(idx, seg_ids_tile, segment_width, lanes)
    xv = x_mat[cols.reshape(-1)]                       # (T*S*L, N)
    contrib = (jnp.where(live, val.astype(jnp.float32), 0.0)
               .reshape(-1)[:, None] * xv)
    acc = jnp.zeros((num_rows_padded, n), jnp.float32)
    return acc.at[rows.reshape(-1)].add(contrib)


def device_arrays(sm: SerpensMatrix):
    """Move a host SerpensMatrix's stream arrays to device (jnp)."""
    cfg = sm.config
    seg_chunks = sm.seg_ids[:: cfg.tiles_per_chunk]
    return (jnp.asarray(sm.idx), jnp.asarray(sm.val),
            jnp.asarray(sm.seg_ids), jnp.asarray(seg_chunks))


def pad_x(x, num_segments, segment_width):
    """Zero-pad a length-K vector to (num_segments * W,)."""
    kp = num_segments * segment_width
    return jnp.pad(x.astype(jnp.float32), (0, kp - x.shape[0]))


def resolve_backend(backend: str | None = None) -> str:
    """Resolve a backend name to a concrete executor ("xla" | "pallas").

    ``None``/``"auto"`` is ``"xla"`` on every platform: the Pallas kernel
    does not lower for the TPU yet (see ``tests/test_chip_compile.py``).
    Bind-time callers (:class:`~repro.core.spmv.SerpensOperator`, the
    service) resolve once and pass the concrete name down.
    """
    if backend is None or backend == "auto":
        return "xla"
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


def _interpret() -> bool:
    """Pallas runs interpreted only off the TPU; on the chip it compiles
    for real or raises, never falling back to the interpreter."""
    return jax.default_backend() != "tpu"


def run_stream(idx, val, seg_ids_tile, seg_ids_chunk, x, *, num_rows_padded,
               segment_width, tiles_per_chunk=1, backend="auto"):
    """The one backend-dispatch point for executing a Serpens stream.

    Accepts a 1-D x (matvec) or a 2-D ``(K_padded, N)`` x (matmat) already
    padded to ``num_segments * segment_width`` rows, and routes to the XLA
    stream execution or the Pallas kernel.  Every executor — single-device,
    per-shard loop, or a ``shard_map`` body — funnels through here, so all
    four (backend x arity) paths share one definition.
    """
    _count_dispatch()
    backend = resolve_backend(backend)
    if backend == "xla":
        if x.ndim == 1:
            return spmv_stream_xla(idx, val, seg_ids_tile, x,
                                   num_rows_padded=num_rows_padded,
                                   segment_width=segment_width)
        return spmm_stream_xla(idx, val, seg_ids_tile, x,
                               num_rows_padded=num_rows_padded,
                               segment_width=segment_width)
    if backend == "pallas":
        interpret = _interpret()
        if x.ndim == 1:
            return serpens_spmv.spmv_pallas(
                idx, val, seg_ids_chunk, x.reshape(-1, segment_width),
                num_rows_padded=num_rows_padded,
                segment_width=segment_width,
                tiles_per_chunk=tiles_per_chunk, interpret=interpret)
        num_segments = x.shape[0] // segment_width
        return serpens_spmv.spmm_pallas(
            idx, val, seg_ids_chunk,
            x.reshape(num_segments, segment_width, -1),
            num_rows_padded=num_rows_padded, segment_width=segment_width,
            tiles_per_chunk=tiles_per_chunk, interpret=interpret)
    raise ValueError(f"unknown backend {backend!r}")


def run_stream_fused(idx, val, seg_ids_tile, seg_ids_chunk, x, *, epilogue,
                     extras=(), num_rows_padded, segment_width,
                     tiles_per_chunk=1, backend="auto"):
    """One-pass matvec **plus** a fused epilogue — the solver hot path.

    ``epilogue(acc2d, *extras) -> tuple of arrays`` runs with the
    (R, LANES) fp32 accumulator still on-chip: on the Pallas backend it is
    traced into the kernel's last grid step
    (:func:`~repro.kernels.serpens_spmv.spmv_fused_pallas`), so one HBM
    pass per solver iteration does the matrix *and* the vector work; on
    the XLA backend it is applied in the same trace immediately after the
    stream scatter, where XLA fuses it with the accumulator while it is
    still in registers/cache.  ``extras`` must be arrays of ≥2 dims
    (scalars as (1, 1)); solver vectors travel in (R, LANES) accumulator
    layout — a pure reshape of the flat vector for square matrices.

    Returns ``(acc, outs)``: flat ``A @ x`` over padded rows, and the
    epilogue outputs.  Counts as ONE stream dispatch
    (:func:`trace_dispatch_count`).
    """
    _count_dispatch()
    extras = tuple(extras)
    backend = resolve_backend(backend)
    if backend == "xla":
        acc = spmv_stream_xla(idx, val, seg_ids_tile, x,
                              num_rows_padded=num_rows_padded,
                              segment_width=segment_width)
        lanes = idx.shape[2]
        outs = epilogue(acc.reshape(-1, lanes), *extras)
        return acc, tuple(outs)
    if backend == "pallas":
        return serpens_spmv.spmv_fused_pallas(
            idx, val, seg_ids_chunk, x.reshape(-1, segment_width), extras,
            epilogue=epilogue, num_rows_padded=num_rows_padded,
            segment_width=segment_width, tiles_per_chunk=tiles_per_chunk,
            interpret=_interpret())
    raise ValueError(f"unknown backend {backend!r}")
