"""Pallas TPU kernel for Serpens SpMV.

Maps the paper's accelerator (Fig. 1) onto the TPU memory hierarchy:

  HBM channel stream      → Pallas grid over fixed-size non-zero *chunks*;
                            the chunk arrays are DMA'd HBM→VMEM by BlockSpec
                            (double-buffered by the Pallas pipeline — the
                            analogue of the paper's Rd modules).
  BRAM x-segment copies   → one x segment (W fp32) staged in VMEM; which
                            segment a chunk needs is a *scalar-prefetch*
                            array (``seg_ids``), the TPU analogue of the
                            paper's "stream x segment, then its non-zeros".
  URAM output accumulators→ the full (R, LANES) fp32 accumulator lives in
                            VMEM across the whole grid (output-stationary;
                            every grid step maps to the same out block).
  8 PEs × row interleave  → lane-stationary rows: lane ℓ owns rows ≡ ℓ
                            (mod LANES); the scatter-add is conflict-free
                            within a tile because preprocessing (format.py)
                            guarantees distinct lane-local rows inside each
                            RAW window.
  CompY (α,β unit)        → fused epilogue in ops.py (y-block already local).

Correctness is validated in ``interpret=True`` mode against ``ref.py``;
the CPU tests ask for the interpreter explicitly.  By default the kernels
compile for the TPU, where Mosaic refuses them today (the ``(1, W)`` x
block, the 1-D gather ``xseg[cols]`` and the scatter-add); see
``tests/test_chip_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.format import ROW_BITS, COL_MASK


def _spmv_kernel(seg_ids_ref, idx_ref, val_ref, x_ref, out_ref):
    """One grid step: process ``tiles_per_chunk`` (sublane × lane) tiles."""
    del seg_ids_ref  # consumed by the BlockSpec index maps only
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]          # (TPC, SUB, LANES) int32 packed
    # bf16-load / fp32-accumulate: the value stream may be bf16 (6 B/slot);
    # upcast is exact, every multiply-accumulate below stays fp32.
    vals = val_ref[...].astype(jnp.float32)
    live = idx != -1
    rows = jnp.where(live, (idx >> ROW_BITS) & COL_MASK, 0)
    cols = jnp.where(live, idx & COL_MASK, 0)

    xseg = x_ref[...][0]        # (W,) — the staged x segment
    xv = xseg[cols]             # on-chip random gather (paper: BRAM reads)
    contrib = jnp.where(live, vals * xv, 0.0)

    lanes = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 2)
    # Lane-stationary scatter (paper: URAM accumulate, II=1 thanks to the
    # RAW-window reordering done offline in format.py).
    out_ref[...] = out_ref[...].at[rows.reshape(-1), lanes.reshape(-1)].add(
        contrib.reshape(-1))


@functools.partial(
    jax.jit,
    static_argnames=("num_rows_padded", "segment_width", "tiles_per_chunk",
                     "interpret"))
def spmv_pallas(idx, val, seg_ids, x2d, *, num_rows_padded, segment_width,
                tiles_per_chunk=1, interpret=False):
    """Raw accumulate ``A @ x`` over the Serpens stream.

    Args:
      idx: int32 [num_tiles, SUB, LANES] packed stream indices.
      val: float32 or bfloat16 [num_tiles, SUB, LANES] stream values
        (accumulation is fp32 either way).
      seg_ids: int32 [num_chunks] segment id per *chunk* (scalar prefetch).
      x2d: float32 [num_segments, W] segment-partitioned dense vector.
      num_rows_padded: R*LANES — accumulator size.
    Returns:
      acc: float32 [num_rows_padded] with acc[r] = (A @ x)[r].
    """
    num_tiles, sub, lanes = idx.shape
    if num_tiles % tiles_per_chunk:
        raise ValueError(
            f"stream has {num_tiles} tiles, not a multiple of "
            f"tiles_per_chunk={tiles_per_chunk}")
    num_chunks = num_tiles // tiles_per_chunk
    if seg_ids.shape != (num_chunks,):
        raise ValueError(
            f"seg_ids shaped {seg_ids.shape}, expected ({num_chunks},) — "
            "a wrong length would silently mis-index x segments")
    r = num_rows_padded // lanes
    w = segment_width

    from jax.experimental.pallas import tpu as pltpu  # deferred import

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_chunks,),
        in_specs=[
            pl.BlockSpec((tiles_per_chunk, sub, lanes),
                         lambda c, seg: (c, 0, 0)),
            pl.BlockSpec((tiles_per_chunk, sub, lanes),
                         lambda c, seg: (c, 0, 0)),
            pl.BlockSpec((1, w), lambda c, seg: (seg[c], 0)),
        ],
        out_specs=pl.BlockSpec((r, lanes), lambda c, seg: (0, 0)),
    )
    acc = pl.pallas_call(
        _spmv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, lanes), jnp.float32),
        interpret=interpret,
    )(seg_ids, idx, val, x2d)
    return acc.reshape(-1)


def _spmm_kernel(seg_ids_ref, idx_ref, val_ref, x_ref, out_ref):
    """Multi-vector variant (the paper's Sextans contrast, Sec. 2.2):
    the x block is (W, N) and each non-zero updates an N-wide row strip.
    Same stream layout and output-stationary accumulation as SpMV."""
    del seg_ids_ref
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]                   # (TPC, SUB, LANES)
    vals = val_ref[...].astype(jnp.float32)   # bf16-load / fp32-accumulate
    live = idx != -1
    rows = jnp.where(live, (idx >> ROW_BITS) & COL_MASK, 0)
    cols = jnp.where(live, idx & COL_MASK, 0)
    xseg = x_ref[...][0]                 # (W, N)
    xv = xseg[cols]                      # (TPC, SUB, LANES, N)
    contrib = jnp.where(live[..., None], vals[..., None] * xv, 0.0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 2)
    out_ref[...] = out_ref[...].at[rows.reshape(-1),
                                   lanes.reshape(-1)].add(
        contrib.reshape(-1, contrib.shape[-1]))


@functools.partial(
    jax.jit,
    static_argnames=("num_rows_padded", "segment_width", "tiles_per_chunk",
                     "interpret"))
def spmm_pallas(idx, val, seg_ids, x3d, *, num_rows_padded, segment_width,
                tiles_per_chunk=1, interpret=False):
    """A @ X for X (num_segments, W, N) → acc (num_rows_padded, N)."""
    from jax.experimental.pallas import tpu as pltpu

    num_tiles, sub, lanes = idx.shape
    if num_tiles % tiles_per_chunk:
        raise ValueError(
            f"stream has {num_tiles} tiles, not a multiple of "
            f"tiles_per_chunk={tiles_per_chunk}")
    num_chunks = num_tiles // tiles_per_chunk
    if seg_ids.shape != (num_chunks,):
        raise ValueError(
            f"seg_ids shaped {seg_ids.shape}, expected ({num_chunks},) — "
            "a wrong length would silently mis-index x segments")
    r = num_rows_padded // lanes
    w = segment_width
    n = x3d.shape[-1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_chunks,),
        in_specs=[
            pl.BlockSpec((tiles_per_chunk, sub, lanes),
                         lambda c, seg: (c, 0, 0)),
            pl.BlockSpec((tiles_per_chunk, sub, lanes),
                         lambda c, seg: (c, 0, 0)),
            pl.BlockSpec((1, w, n), lambda c, seg: (seg[c], 0, 0)),
        ],
        out_specs=pl.BlockSpec((r, lanes, n),
                               lambda c, seg: (0, 0, 0)),
    )
    acc = pl.pallas_call(
        _spmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, lanes, n), jnp.float32),
        interpret=interpret,
    )(seg_ids, idx, val, x3d)
    return acc.reshape(num_rows_padded, n)


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "num_rows_padded", "segment_width",
                     "tiles_per_chunk", "interpret"))
def spmv_fused_pallas(idx, val, seg_ids, x2d, extras=(), *, epilogue,
                      num_rows_padded, segment_width, tiles_per_chunk=1,
                      interpret=False):
    """``A @ x`` with a fused epilogue in the kernel's output tile loop.

    Identical streaming/accumulation to :func:`spmv_pallas`, but on the
    *last* grid step — while the (R, LANES) accumulator is still resident
    in VMEM — ``epilogue(acc, *extras)`` runs inside the kernel and its
    results are written out alongside the accumulator.  This is how a
    solver iteration's vector work (axpy/dot/normalize) shares the matrix
    pass's single trip over HBM: the paper's CompY (α,β) unit generalized
    to arbitrary per-iteration vector algebra.

      * ``epilogue`` — a traceable pure fn ``(acc2d, *extras) -> tuple of
        arrays``; ``acc2d`` is the (R, LANES) fp32 accumulator.  Must be
        hashable (module-level function), it is a static jit arg.
      * ``extras`` — tuple of arrays (each ≥2-D for TPU tiling; scalars
        travel as (1, 1) arrays).  They are staged whole into VMEM —
        solver vectors in (R, LANES) layout, which for square matrices is
        a pure reshape of the flat vector (row r = rr * LANES + lane).

    Returns ``(acc, outs)``: the flat accumulator and the epilogue's
    outputs.
    """
    from jax.experimental.pallas import tpu as pltpu

    num_tiles, sub, lanes = idx.shape
    if num_tiles % tiles_per_chunk:
        raise ValueError(
            f"stream has {num_tiles} tiles, not a multiple of "
            f"tiles_per_chunk={tiles_per_chunk}")
    num_chunks = num_tiles // tiles_per_chunk
    if seg_ids.shape != (num_chunks,):
        raise ValueError(
            f"seg_ids shaped {seg_ids.shape}, expected ({num_chunks},) — "
            "a wrong length would silently mis-index x segments")
    r = num_rows_padded // lanes
    w = segment_width
    extras = tuple(extras)
    n_extra = len(extras)
    out_sds = jax.eval_shape(
        epilogue, jax.ShapeDtypeStruct((r, lanes), jnp.float32),
        *(jax.ShapeDtypeStruct(e.shape, e.dtype) for e in extras))
    out_sds = tuple(out_sds)

    def kernel(seg_ids_ref, idx_ref, val_ref, x_ref, *refs):
        extra_refs = refs[:n_extra]
        acc_ref = refs[n_extra]
        out_refs = refs[n_extra + 1:]
        c = pl.program_id(0)

        @pl.when(c == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            for o in out_refs:
                o[...] = jnp.zeros_like(o)

        idx_t = idx_ref[...]
        vals = val_ref[...].astype(jnp.float32)
        live = idx_t != -1
        rows = jnp.where(live, (idx_t >> ROW_BITS) & COL_MASK, 0)
        cols = jnp.where(live, idx_t & COL_MASK, 0)
        xseg = x_ref[...][0]
        xv = xseg[cols]
        contrib = jnp.where(live, vals * xv, 0.0)
        lanes_i = jax.lax.broadcasted_iota(jnp.int32, idx_t.shape, 2)
        acc_ref[...] = acc_ref[...].at[
            rows.reshape(-1), lanes_i.reshape(-1)].add(contrib.reshape(-1))

        @pl.when(c == num_chunks - 1)
        def _epilogue():
            # The last chunk's accumulation above has already executed,
            # so acc is the complete A @ x.
            outs = epilogue(acc_ref[...],
                            *(e[...] for e in extra_refs))
            for o_ref, o in zip(out_refs, outs):
                o_ref[...] = o.astype(o_ref.dtype)

    def resident(shape):             # whole array staged, every grid step
        return pl.BlockSpec(shape, lambda c, seg: (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_chunks,),
        in_specs=[
            pl.BlockSpec((tiles_per_chunk, sub, lanes),
                         lambda c, seg: (c, 0, 0)),
            pl.BlockSpec((tiles_per_chunk, sub, lanes),
                         lambda c, seg: (c, 0, 0)),
            pl.BlockSpec((1, w), lambda c, seg: (seg[c], 0)),
        ] + [resident(e.shape) for e in extras],
        out_specs=[pl.BlockSpec((r, lanes), lambda c, seg: (0, 0))]
        + [resident(s.shape) for s in out_sds],
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, lanes), jnp.float32)]
        + list(out_sds),
        interpret=interpret,
    )(seg_ids, idx, val, x2d, *extras)
    return res[0].reshape(-1), tuple(res[1:])
