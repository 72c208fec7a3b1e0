"""The row-ordered XLA executor (``kernels/ops.py``).

Each case runs the operator on the default XLA backend and compares it
with a dense float64 reference over the plan's own (stream-rounded)
values, and each shard's executor with the scatter-add executor it
replaced, kept here as an oracle over the same Serpens stream.  Both sum a
row's products in fp32, in different orders, so they agree to the rounding
of a sum of that many terms, and bitwise on rows of at most two products.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import format as F
from repro.core import partition as P
from repro.core.registry import MatrixRegistry
from repro.core.spmv import SerpensOperator
from repro.data import matrices as M
from repro.kernels import ops
from repro.serve.spmv_service import SpMVService
from repro.solvers.power_iteration import _pagerank_epilogue

CFG = F.SerpensConfig(segment_width=64, lanes=8, sublanes=4, raw_window=4)
SPILL_CFG = F.SerpensConfig(segment_width=32, lanes=4, sublanes=4,
                            raw_window=2, spill_hot_rows=True,
                            lane_balance=1.2)
U = 2.0 ** -24                          # fp32 unit roundoff


@functools.partial(jax.jit, static_argnames=("num_rows_padded",
                                             "segment_width"))
def scatter_oracle(idx, val, seg_ids, x, *, num_rows_padded,
                   segment_width):
    """The executor before the row order: decode the Serpens stream and
    scatter-add every slot's product into its row (x 1-D or (K, N))."""
    lanes = idx.shape[2]
    live = idx != -1
    rows = (jnp.where(live, (idx >> F.ROW_BITS) & F.COL_MASK, 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, idx.shape, 2))
    cols = (seg_ids[:, None, None] * segment_width
            + jnp.where(live, idx & F.COL_MASK, 0))
    v = jnp.where(live, val.astype(jnp.float32), 0.0).reshape(-1)
    xv = x[cols.reshape(-1)]
    contrib = v * xv if x.ndim == 1 else v[:, None] * xv
    acc = jnp.zeros((num_rows_padded,) + x.shape[1:], jnp.float32)
    return acc.at[rows.reshape(-1)].add(contrib)


def _long_row():
    """Row 3 holds more products than two levels of scan blocks."""
    rng = np.random.default_rng(1)
    long_n = ops.BLOCK * ops.BLOCK + 3 * ops.BLOCK + 5
    rows = np.r_[np.full(long_n, 3), rng.integers(0, 40, 600)]
    cols = np.r_[np.arange(long_n) * 2, rng.integers(0, 2 * long_n, 600)]
    vals = rng.normal(size=rows.size).astype(np.float32)
    return rows, cols, vals, (40, 2 * long_n)


def _empty_rows():
    """Rows 0, 2, 4, … < 150 only: empty rows in the middle and a padded
    tail (203 rows round up to 208 accumulator rows)."""
    rng = np.random.default_rng(2)
    rows = 2 * rng.integers(0, 75, 900)
    cols = rng.integers(0, 170, 900)
    return rows, cols, rng.normal(size=900).astype(np.float32), (203, 170)


def _uniform():
    r, c, v = M.uniform_random(90, 130, 1500, seed=3)
    return r, c, v, (90, 130)


def _power_law():
    r, c, v = M.power_law_graph(150, 2000, seed=4)
    return r, c, v, (150, 150)


def _stochastic():
    r, c, v = M.power_law_graph(120, 900, seed=5)
    return r, c, M.column_normalize(r, c, v, 120), (120, 120)


# name: (matrix, config, plan spec)
CASES = {
    "long_row": (_long_row, CFG, P.PlanSpec()),
    "empty_rows": (_empty_rows, CFG, P.PlanSpec()),
    "bf16": (_uniform, dataclasses.replace(CFG, value_dtype="bfloat16"),
             P.PlanSpec()),
    "row_shards": (_power_law, CFG, P.PlanSpec("row", 3)),
    "col_shards": (_power_law, CFG, P.PlanSpec("col", 2)),
    "aux_spill": (_power_law, SPILL_CFG, P.PlanSpec()),
    "balanced": (_power_law, CFG, P.PlanSpec("row", 2, "balanced")),
    "fused_pagerank": (_stochastic, CFG, P.PlanSpec()),
}


def _dense(rows, cols, vals, shape):
    a = np.zeros(shape, np.float64)
    np.add.at(a, (rows, cols), vals.astype(np.float64))
    return a


def _gamma(terms: int) -> float:
    """Relative rounding bound of an fp32 sum of ``terms`` products."""
    return (terms + 1) * U / (1 - (terms + 1) * U)


def _check_row_order(sm, keys, vals, ends):
    """The layout :func:`ops.row_order` promises for one shard."""
    keys, vals, ends = map(np.asarray, (keys, vals, ends))
    live = int((sm.idx != F.SENTINEL).sum())
    assert keys.size % ops.BLOCK == 0 and keys.size > live
    assert np.all(keys[live:] < 0) and np.all(vals[live:] == 0)
    r, _, _ = F.decode_to_coo(sm)
    counts = np.bincount(r[:live], minlength=sm.padded_rows)
    assert int((keys[:live] < 0).sum()) == int((counts > 0).sum())
    assert np.all(ends[counts == 0] == keys.size - 1)
    np.testing.assert_array_equal(np.cumsum(counts)[counts > 0] - 1,
                                  ends[counts > 0])


@pytest.mark.parametrize("n", [1, 2, 4, 16])
@pytest.mark.parametrize("case", list(CASES))
def test_row_segmented_matches_dense_and_scatter(case, n):
    matrix, cfg, spec = CASES[case]
    rows, cols, vals, shape = matrix()
    plan = P.make_plan(rows, cols, vals, shape, cfg, spec)
    op = SerpensOperator(plan, backend="xla")
    assert op.cost_report()["executor_path"] == "row_segmented"
    rng = np.random.default_rng(n)
    x = rng.normal(size=shape[1:] + ((n,) if n > 1 else ())).astype(
        np.float32)
    y = np.asarray(op.matvec(x) if n == 1 else op.matmat(x), np.float64)

    # Whole operator against float64 on the values the stream holds.
    a = _dense(*plan.to_coo(), shape)
    terms = int((a != 0).sum(axis=1).max()) + plan.num_shards
    ref = a @ x.astype(np.float64)
    mag = np.abs(a) @ np.abs(x.astype(np.float64))
    assert np.all(np.abs(y - ref) <= _gamma(terms) * mag)

    # Each shard's executor against the scatter-add oracle.
    kp = plan.num_segments_local * cfg.segment_width
    pad = [(0, plan.num_shards * kp - shape[1])] + [(0, 0)] * (x.ndim - 1)
    xp = np.pad(x, pad)
    for d, sm in enumerate(plan.shards):
        dev = ops.device_arrays(sm)
        _check_row_order(sm, dev.keys, dev.vals, dev.ends)
        xl = jnp.asarray(xp[d * kp:(d + 1) * kp] if spec.partition == "col"
                         else xp[:kp])
        kw = dict(num_rows_padded=sm.padded_rows,
                  segment_width=cfg.segment_width)
        got = np.asarray(ops.run_stream(dev, xl, backend="xla", **kw))
        old = np.asarray(scatter_oracle(dev.idx, dev.val,
                                        jnp.asarray(sm.seg_ids), xl, **kw))
        r, c, v = F.decode_to_coo(sm)
        live = r.size - sm.n_aux
        a_s = np.abs(_dense(r[:live], c[:live], v[:live],
                            (sm.padded_rows, kp)))
        counts = (a_s != 0).sum(axis=1)
        mag_s = a_s @ np.abs(np.asarray(xl, np.float64))
        assert np.all(np.abs(got - old)
                      <= 2 * _gamma(int(counts.max())) * mag_s)
        np.testing.assert_array_equal(got[counts <= 2], old[counts <= 2])

    if case == "fused_pagerank" and n == 1:
        lanes = cfg.lanes
        r2 = op.to_acc_layout(np.abs(x) / np.abs(x).sum())
        mask2 = op.to_acc_layout(np.ones(shape[0], np.float32))
        consts = jnp.asarray([[0.85, shape[0]]], jnp.float32)
        acc, (r_new, delta) = op.matvec_fused(
            np.asarray(op.from_acc_layout(r2)), _pagerank_epilogue,
            extras=(r2, mask2, consts))
        xs = np.asarray(op.from_acc_layout(r2), np.float64)
        acc_ref = np.pad(a @ xs, (0, plan.out_rows_padded - shape[0]))
        mag_r = np.pad(np.abs(a) @ xs, (0, plan.out_rows_padded - shape[0]))
        bound = _gamma(terms) * mag_r
        assert np.all(np.abs(np.asarray(acc) - acc_ref) <= bound)
        want = _pagerank_epilogue(jnp.asarray(acc_ref.reshape(-1, lanes),
                                              jnp.float32), r2, mask2,
                                  consts)
        np.testing.assert_allclose(np.asarray(r_new), np.asarray(want[0]),
                                   rtol=0, atol=0.85 * (bound.max()
                                                        + bound.sum()) + 1e-7)


def test_executor_calls_counts_one_per_dispatched_batch():
    rows, cols, vals, shape = _uniform()
    reg = MatrixRegistry(config=CFG, backend="xla")
    mid = reg.put(rows, cols, vals, shape)
    assert reg.get(mid).cost_report()["executor_path"] == "row_segmented"
    xs = np.random.default_rng(0).normal(size=(7, shape[1])).astype(
        np.float32)
    for backend, path, other in (("xla", "row_segmented", "scatter"),
                                 ("pallas", "scatter", "row_segmented")):
        svc = SpMVService(reg, max_bucket=4, backend=backend)
        calls = svc.metrics.get("executor_calls")
        for x in xs:
            svc.submit(mid, x)
        svc.flush()
        assert svc.stats.batches == 2            # 4 + 3 requests
        assert calls.value(path=path) == 2
        assert calls.value(path=other) == 0
        svc.submit(mid, xs[0])
        svc.flush()
        assert calls.value(path=path) == svc.stats.batches == 3
