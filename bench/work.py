"""Work counts of SpMV/SpMM calls, from the matrix and the vectors alone.

The roofline divides these by the device's busy time, so they must read
the same whatever format, plan or kernel serves the call: nothing here
looks at padding slots, spilled rows, lane assignment or padded SpMM
columns, which are waste and not work.  A call that serves ``n_vectors``
vectors reads the matrix once and each vector and result once:

    bytes = nnz * (4 + value_bytes) + 4 * n_vectors * (rows + cols)
    flops = 2 * nnz * n_vectors

with 4 bytes of index per non-zero.  A format that stores less index
than that could read above its roofline; see ``PERF.md``.

A plan over several chips counts the same: a row plan reads x once on
each chip (x is replicated to every chip of the mesh), and a column plan
writes a partial y on each; those copies are waste, not work.  The
roofline divides these counts by the peak of all the cell's chips
together (``trace_reduce.roofline_pct``).
"""
from __future__ import annotations

VALUE_BYTES = {"float32": 4, "bfloat16": 2}
INDEX_BYTES = 4
VECTOR_BYTES = 4          # fp32 x and y


def spmv_work(nnz: int, shape: tuple[int, int], value_dtype: str,
              n_vectors: int = 1) -> tuple[int, int]:
    """``(bytes, flops)`` of one call serving ``n_vectors`` vectors."""
    if n_vectors < 1:
        raise ValueError("a call serves at least one vector")
    rows, cols = shape
    value_bytes = VALUE_BYTES[value_dtype]
    nbytes = (nnz * (INDEX_BYTES + value_bytes)
              + VECTOR_BYTES * n_vectors * (rows + cols))
    return nbytes, 2 * nnz * n_vectors


def window_work(nnz: int, shape: tuple[int, int], value_dtype: str,
                calls: int, vectors: int) -> tuple[int, int]:
    """``(bytes, flops)`` of ``calls`` calls that served ``vectors``
    vectors between them: the sum of :func:`spmv_work` over the calls,
    which is linear in each call's vector count."""
    if calls == 0:
        return 0, 0
    if vectors < calls:
        raise ValueError("each call serves at least one vector")
    b1, f1 = spmv_work(nnz, shape, value_dtype, 1)
    vector_bytes = VECTOR_BYTES * (shape[0] + shape[1])
    return (calls * (b1 - vector_bytes) + vectors * vector_bytes,
            vectors * f1)
