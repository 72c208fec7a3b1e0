"""Jit'd wrappers around the Serpens kernels + the XLA stream executor.

Three execution paths, selectable via ``backend=``:

  * ``"xla"``       — the stream's live slots in row order: one gather
                      of x, then each row's products summed by a
                      segmented scan (no scatter over the non-zeros).  It
                      compiles for the TPU (v5e) at published matrix
                      sizes and is the default on every platform.
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
import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.format import COL_MASK, ROW_BITS, SENTINEL, SerpensMatrix
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


# -- the row-ordered XLA executor ---------------------------------------
# Bind time (device_arrays) lays each shard's live slots out in
# destination-row order.  Per call the products vals * x[cols] are formed
# in that order and each row's consecutive products are summed by a
# segmented scan, read at the row's last product: nothing scatters over
# the non-zeros, and each row's sum is fp32 over its own products only.

BLOCK = 128                                  # scan block: one vreg's lanes
_ROW_START = np.int32(np.iinfo(np.int32).min)  # sign bit of a key
_COL = np.int32(np.iinfo(np.int32).max)        # the column under it


class ShardArrays(NamedTuple):
    """One shard's device buffers.

    ``idx``, ``val``, ``seg_chunk``: the Serpens stream the Pallas kernel
    reads.  ``keys``, ``vals``, ``ends``: the same live slots in row order
    for the XLA executor (see :func:`row_order`).
    """
    idx: jax.Array
    val: jax.Array
    seg_chunk: jax.Array
    keys: jax.Array
    vals: jax.Array
    ends: jax.Array


def row_slots(sm: SerpensMatrix) -> int:
    """Length of a shard's row-ordered arrays: its live stream slots plus
    at least one zero slot, rounded up to :data:`BLOCK`."""
    return -(-(sm.nnz - sm.n_aux + 1) // BLOCK) * BLOCK


def row_order(sm: SerpensMatrix, length: int | None = None):
    """A shard's live stream slots in destination-row order (host numpy).

    Rows and columns decode exactly as the Pallas kernel reads the stream
    (shard-local, virtual rows for balanced plans); the aux spill stays
    out.  Returns ``(keys, vals, ends)``:

    * ``keys`` int32 ``[length]``: each product's column, with the sign
      bit set on a row's first product.  ``length`` (by default, and at
      least, :func:`row_slots`) leaves zero tail slots, each a row of its
      own.
    * ``vals`` ``[length]``, in the stream's value dtype.
    * ``ends`` int32 ``[padded_rows]``: each row's last position; an empty
      row points at the last (zero) slot.

    Within a row the stream's order is kept.  A row lives in one lane, so
    each lane is sorted by its lane-local row on its own (a 16-bit radix
    sort over a lane's slots, in parallel threads) and then placed at its
    rows' offsets.
    """
    cfg = sm.config
    lanes = cfg.lanes
    idx = sm.idx.reshape(-1, lanes)
    val = sm.val.reshape(-1, lanes)
    col0 = np.repeat(sm.seg_ids * cfg.segment_width, cfg.sublanes)
    per_lane = sm.padded_rows // lanes
    length = length or row_slots(sm)

    def sort_lane(lane):
        word = idx[:, lane]
        at = np.flatnonzero(word != SENTINEL)
        order = np.argsort((word[at] >> ROW_BITS).astype(np.uint16),
                           kind="stable")
        at = at[order]
        word = word[at]
        local = (word >> ROW_BITS) & COL_MASK
        return at, word, local, np.bincount(local, minlength=per_lane)

    with ThreadPoolExecutor(min(lanes, os.cpu_count() or 1)) as pool:
        by_lane = list(pool.map(sort_lane, range(lanes)))
        counts = np.stack([b[3] for b in by_lane], axis=1).reshape(-1)
        ends = np.cumsum(counts, dtype=np.int32) - 1
        starts = ends - counts + 1
        keys = np.full(length, _ROW_START, np.int32)
        vals = np.zeros(length, sm.val.dtype)

        def place(lane):
            at, word, local, count = by_lane[lane]
            shift = starts[lane::lanes] - (np.cumsum(count) - count)
            pos = np.arange(at.size) + shift[local]
            keys[pos] = col0[at] + (word & COL_MASK)
            vals[pos] = val[at, lane]

        list(pool.map(place, range(lanes)))
    keys[starts[counts > 0]] |= _ROW_START
    ends[counts == 0] = length - 1
    return keys, vals, ends


def _shift(a, d, fill):
    """``a`` moved ``d`` places along its last axis, ``fill`` shifted in."""
    pad = [(0, 0)] * (a.ndim - 1) + [(d, 0)]
    return jnp.pad(a[..., :-d], pad, constant_values=fill)


def _segmented_scan(v, start):
    """Inclusive sum of ``v`` along its last axis, restarted at ``start``.

    Hillis–Steele steps inside blocks of :data:`BLOCK`; the same scan over
    the blocks' last values then carries a run across block edges.
    ``start`` (bool, the last axis alone) broadcasts over ``v``'s leading
    axes.
    """
    *lead, n = v.shape
    nb = -(-n // BLOCK)
    pad = nb * BLOCK - n
    v = jnp.pad(v, [(0, 0)] * len(lead) + [(0, pad)])
    v = v.reshape(*lead, nb, BLOCK)
    f = jnp.pad(start, (0, pad), constant_values=True).reshape(nb, BLOCK)
    d = 1
    while d < min(n, BLOCK):
        v = jnp.where(f, v, v + _shift(v, d, 0.0))
        f = f | _shift(f, d, False)
        d *= 2
    if nb > 1:
        run = _segmented_scan(v[..., -1], f[:, -1])
        carry = _shift(run, 1, 0.0)
        v = v + jnp.where(f, 0.0, carry[..., None])
    return v.reshape(*lead, nb * BLOCK)[..., :n]


def _check_rows(ends, num_rows_padded):
    if ends.shape != (num_rows_padded,):
        raise ValueError(f"ends has shape {ends.shape}, expected "
                         f"({num_rows_padded},)")


def _row_sums(keys, vals, xg, ends):
    """fp32 row sums of ``vals * xg`` along the row-ordered slots."""
    prod = vals.astype(jnp.float32) * xg
    return jnp.take(_segmented_scan(prod, keys < 0), ends)


@functools.partial(jax.jit, static_argnames=("num_rows_padded",
                                             "segment_width"))
def spmv_stream_xla(keys, vals, ends, x_flat, *, num_rows_padded,
                    segment_width):
    """Row-ordered XLA execution of one shard (see :func:`row_order`):
    ``A @ x`` over the ``num_rows_padded`` rows of ``ends``."""
    del segment_width                      # the keys hold global columns
    _check_rows(ends, num_rows_padded)
    # bf16-load / fp32-accumulate: the upcast is exact, the sums are f32.
    return _row_sums(keys, vals, x_flat[keys & _COL], ends)


@functools.partial(jax.jit, static_argnames=("num_rows_padded",
                                             "segment_width"))
def spmm_stream_xla(keys, vals, ends, x_mat, *, num_rows_padded,
                    segment_width):
    """Multi-vector :func:`spmv_stream_xla`: x_mat is (K_padded, N) →
    (R_padded, N).  One gather fetches each product's N-wide row of x;
    the vectors' row sums then run one after another, so the scan stays
    one-dimensional (its compile and its temporaries do not grow with N).
    """
    del segment_width
    _check_rows(ends, num_rows_padded)
    xg = jnp.take(x_mat.T, keys & _COL, axis=1)            # (N, L)
    return jax.lax.map(lambda g: _row_sums(keys, vals, g, ends), xg).T


def device_arrays(sm: SerpensMatrix) -> ShardArrays:
    """Move a host SerpensMatrix to the device: its stream and its
    row-ordered copy (:func:`row_order`)."""
    cfg = sm.config
    return ShardArrays(
        jnp.asarray(sm.idx), jnp.asarray(sm.val),
        jnp.asarray(sm.seg_ids[:: cfg.tiles_per_chunk]),
        *(jnp.asarray(a) for a in row_order(sm)))


def executor_path(backend: str | None = None) -> str:
    """How ``backend`` sums each row's products: ``"row_segmented"`` (the
    XLA executor) or ``"scatter"`` (the Pallas kernel's accumulate)."""
    return ("row_segmented" if resolve_backend(backend) == "xla"
            else "scatter")


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


def run_stream(arrays: ShardArrays, x, *, num_rows_padded, segment_width,
               tiles_per_chunk=1, backend="auto"):
    """The one backend-dispatch point for executing a shard.

    Accepts a 1-D x (matvec) or a 2-D ``(K_padded, N)`` x (matmat) already
    padded to ``num_segments * segment_width`` rows, and routes to the XLA
    row-ordered executor or the Pallas kernel over the Serpens stream.
    Every executor — single-device, per-shard loop, or a ``shard_map``
    body — funnels through here, so all four (backend x arity) paths
    share one definition.
    """
    _count_dispatch()
    backend = resolve_backend(backend)
    if backend == "xla":
        xla = spmv_stream_xla if x.ndim == 1 else spmm_stream_xla
        return xla(arrays.keys, arrays.vals, arrays.ends, x,
                   num_rows_padded=num_rows_padded,
                   segment_width=segment_width)
    if backend == "pallas":
        idx, val, seg_chunk = arrays.idx, arrays.val, arrays.seg_chunk
        interpret = _interpret()
        if x.ndim == 1:
            return serpens_spmv.spmv_pallas(
                idx, val, seg_chunk, x.reshape(-1, segment_width),
                num_rows_padded=num_rows_padded,
                segment_width=segment_width,
                tiles_per_chunk=tiles_per_chunk, interpret=interpret)
        num_segments = x.shape[0] // segment_width
        return serpens_spmv.spmm_pallas(
            idx, val, seg_chunk,
            x.reshape(num_segments, segment_width, -1),
            num_rows_padded=num_rows_padded, segment_width=segment_width,
            tiles_per_chunk=tiles_per_chunk, interpret=interpret)
    raise ValueError(f"unknown backend {backend!r}")


def run_stream_fused(arrays: ShardArrays, x, *, epilogue, extras=(),
                     num_rows_padded, segment_width, tiles_per_chunk=1,
                     backend="auto"):
    """One-pass matvec **plus** a fused epilogue — the solver hot path.

    ``epilogue(acc2d, *extras) -> tuple of arrays`` runs with the
    (R, LANES) fp32 accumulator still on-chip: on the Pallas backend it is
    traced into the kernel's last grid step
    (:func:`~repro.kernels.serpens_spmv.spmv_fused_pallas`), so one HBM
    pass per solver iteration does the matrix *and* the vector work; on
    the XLA backend it is applied in the same trace immediately after the
    row sums, where XLA fuses it with the accumulator.  ``extras`` must
    be arrays of ≥2 dims (scalars as (1, 1)); solver vectors travel in
    (R, LANES) accumulator layout — a pure reshape of the flat vector for
    square matrices.

    Returns ``(acc, outs)``: flat ``A @ x`` over padded rows, and the
    epilogue outputs.  Counts as ONE stream dispatch
    (:func:`trace_dispatch_count`).
    """
    _count_dispatch()
    extras = tuple(extras)
    backend = resolve_backend(backend)
    lanes = arrays.idx.shape[-1]
    if backend == "xla":
        acc = spmv_stream_xla(arrays.keys, arrays.vals, arrays.ends, x,
                              num_rows_padded=num_rows_padded,
                              segment_width=segment_width)
        outs = epilogue(acc.reshape(-1, lanes), *extras)
        return acc, tuple(outs)
    if backend == "pallas":
        return serpens_spmv.spmv_fused_pallas(
            arrays.idx, arrays.val, arrays.seg_chunk,
            x.reshape(-1, segment_width), extras, epilogue=epilogue,
            num_rows_padded=num_rows_padded, segment_width=segment_width,
            tiles_per_chunk=tiles_per_chunk, interpret=_interpret())
    raise ValueError(f"unknown backend {backend!r}")
