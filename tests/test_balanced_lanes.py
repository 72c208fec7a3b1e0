"""lane_assign="balanced": LPT virtual-row permutation correctness.

The maxE-inspired least-loaded lane assignment replaces the modulo lane
split with a longest-processing-time greedy pack; the permutation rides
on the plan (``row_perm``) and the operator gathers the output back, so
the contract is bit-exact round-trip + matvec parity with the modulo
path, plus an actual padded-slot reduction on skewed matrices when
paired with hot-row spill.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import format as F
from repro.core import partition as PT
from repro.core.registry import MatrixRegistry
from repro.core.spmv import SerpensOperator
from repro.data import matrices as M

CFG = F.SerpensConfig(segment_width=64, lanes=8, sublanes=4, raw_window=4)
SPILL_CFG = dataclasses.replace(CFG, spill_hot_rows=True, lane_balance=1.1)


def rand_coo(m, k, nnz, seed=0):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m, nnz)
    cols = rng.integers(0, k, nnz)
    vals = rng.normal(size=nnz).astype(np.float32)
    return rows, cols, vals


def dense_of(rows, cols, vals, shape):
    d = np.zeros(shape, np.float64)
    np.add.at(d, (rows, cols), vals)
    return d


def coo_multiset(rows, cols, vals, shape):
    key = np.asarray(rows, np.int64) * shape[1] + np.asarray(cols)
    order = np.argsort(key, kind="stable")
    return key[order], np.asarray(vals)[order]


class TestLPTAssignment:
    def test_injective_and_bounded(self):
        rng = np.random.default_rng(0)
        for _ in range(20):
            n = int(rng.integers(1, 100))
            lanes = int(rng.choice([2, 4, 8]))
            counts = rng.integers(0, 50, n)
            virt = PT.balanced_virtual_rows(counts, lanes)
            assert virt.size == n
            assert len(set(virt.tolist())) == n          # injective
            assert virt.max() < -(-n // lanes) * lanes   # bounded

    def test_heavy_rows_spread_across_lanes(self):
        # 4 heavy rows + light rows, 4 lanes: LPT must give each heavy
        # row its own lane; modulo (all heavy at 0,1,2,3) does too here,
        # so make them collide: heavy rows all ≡ 0 (mod lanes).
        lanes = 4
        counts = np.ones(16, np.int64)
        counts[[0, 4, 8, 12]] = 100
        virt = PT.balanced_virtual_rows(counts, lanes)
        heavy_lanes = sorted(virt[[0, 4, 8, 12]] % lanes)
        assert heavy_lanes == [0, 1, 2, 3]

    def test_block_local_for_row_partition(self):
        m, k, nnz = 64, 48, 600
        rows, cols, vals = rand_coo(m, k, nnz, seed=1)
        prep = F.prepare(rows, cols, vals, (m, k), CFG)
        spec = PT.PlanSpec("row", 2, "balanced")
        block_m = -(-m // 2)
        perm = PT.balanced_row_perm(prep, spec, block_m)
        # A row stays inside its shard's block.
        assert np.array_equal(np.arange(m) // block_m, perm // block_m)

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            PT.PlanSpec("single", 1, "zigzag")
        assert PT.PlanSpec("single", 1).lane_assign == "modulo"


@pytest.mark.parametrize("partition,num_shards", [
    ("single", 1), ("row", 2), ("col", 2)])
@pytest.mark.parametrize("cfg", [CFG, SPILL_CFG],
                         ids=["plain", "spill+lb"])
def test_roundtrip_bit_exact(partition, num_shards, cfg):
    """to_coo of a balanced plan returns the exact original multiset."""
    rows, cols, vals = rand_coo(72, 80, 700, seed=2)
    plan = PT.make_plan(rows, cols, vals, (72, 80), cfg,
                        PT.PlanSpec(partition, num_shards, "balanced"))
    assert plan.row_perm is not None
    r2, c2, v2 = plan.to_coo()
    k1, v1s = coo_multiset(rows, cols, vals, (72, 80))
    k2, v2s = coo_multiset(r2, c2, v2, (72, 80))
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(np.sort(v1s), np.sort(v2s))


HAVE_HYP = True
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    HAVE_HYP = False

if HAVE_HYP:
    @settings(max_examples=40, deadline=None)
    @given(st.integers(1, 80), st.integers(1, 90), st.integers(1, 400),
           st.integers(0, 10_000),
           st.sampled_from(["single", "row", "col"]),
           st.booleans())
    def test_property_roundtrip_bit_exact(m, k, nnz, seed, partition,
                                          spill):
        rows, cols, vals = rand_coo(m, k, nnz, seed)
        cfg = SPILL_CFG if spill else CFG
        plan = PT.make_plan(rows, cols, vals, (m, k), cfg,
                            PT.PlanSpec(partition,
                                        1 if partition == "single" else 2,
                                        "balanced"))
        r2, c2, v2 = plan.to_coo()
        k1, _ = coo_multiset(rows, cols, vals, (m, k))
        k2, _ = coo_multiset(r2, c2, v2, (m, k))
        np.testing.assert_array_equal(k1, k2)
        np.testing.assert_allclose(
            dense_of(r2, c2, v2, (m, k)),
            dense_of(rows, cols, vals, (m, k)), rtol=0, atol=0)


@pytest.mark.parametrize("partition,num_shards", [
    ("single", 1), ("row", 2), ("col", 2)])
def test_matvec_matches_modulo(partition, num_shards):
    rows, cols, vals = rand_coo(96, 64, 900, seed=3)
    x = np.random.default_rng(4).normal(size=64).astype(np.float32)
    dense = dense_of(rows, cols, vals, (96, 64))
    ys = {}
    for assign in ("modulo", "balanced"):
        plan = PT.make_plan(rows, cols, vals, (96, 64), SPILL_CFG,
                            PT.PlanSpec(partition, num_shards, assign))
        op = SerpensOperator(plan, backend="xla")
        ys[assign] = np.asarray(op.matvec(x))
        np.testing.assert_allclose(ys[assign], dense @ x,
                                   atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(ys["balanced"], ys["modulo"],
                               atol=1e-5, rtol=1e-5)


def test_matmat_and_output_order(self_n=64):
    rows, cols, vals = M.power_law_graph(self_n, self_n * 8, seed=5)
    dense = dense_of(rows, cols, vals, (self_n, self_n))
    xs = np.random.default_rng(6).normal(size=(self_n, 3)) \
        .astype(np.float32)
    plan = PT.make_plan(rows, cols, vals, (self_n, self_n), SPILL_CFG,
                        PT.PlanSpec("single", 1, "balanced"))
    op = SerpensOperator(plan, backend="xla")
    np.testing.assert_allclose(np.asarray(op.matmat(xs)), dense @ xs,
                               atol=1e-3, rtol=1e-3)


def test_padded_slots_reduced_on_power_law():
    """Acceptance: with hot-row spill, LPT lanes pad measurably less
    than modulo on a power-law matrix."""
    n = 512
    rows, cols, vals = M.power_law_graph(n, 8000, seed=3)
    # Spill on, threshold at its default: hot rows leave the stream, so
    # per-lane entry totals dominate the schedule — the regime LPT fixes.
    cfg = F.SerpensConfig(segment_width=256, lanes=16, sublanes=8,
                          spill_hot_rows=True)
    slots = {}
    for assign in ("modulo", "balanced"):
        plan = PT.make_plan(rows, cols, vals, (n, n), cfg,
                            PT.PlanSpec("single", 1, assign))
        slots[assign] = int(plan.idx.size)
    assert slots["balanced"] < slots["modulo"], slots
    # Meaningful, not epsilon: >= 10% fewer padded slots.
    assert slots["balanced"] <= 0.9 * slots["modulo"], slots


def test_cost_report_shows_lane_assign_and_imbalance():
    rows, cols, vals = M.power_law_graph(256, 4000, seed=7)
    for assign in ("modulo", "balanced"):
        plan = PT.make_plan(rows, cols, vals, (256, 256), SPILL_CFG,
                            PT.PlanSpec("single", 1, assign))
        rep = SerpensOperator(plan, backend="xla").cost_report()
        assert rep["lane_assign"] == assign
        assert rep["lane_slot_imbalance"] >= 1.0
        assert all(s["lane_slot_imbalance"] >= 1.0 for s in rep["shards"])


def test_fused_epilogue_rejected():
    rows, cols, vals = rand_coo(48, 48, 300, seed=8)
    plan = PT.make_plan(rows, cols, vals, (48, 48), CFG,
                        PT.PlanSpec("single", 1, "balanced"))
    op = SerpensOperator(plan, backend="xla")
    assert not op.supports_fused_epilogue
    with pytest.raises(ValueError, match="lane_assign"):
        op.matvec_fused(np.zeros(48, np.float32),
                        lambda acc: (acc,))


def test_delta_update_rejected_then_reencoded():
    """plan_apply_delta refuses balanced plans; registry.update falls
    back to a full re-encode and stays correct."""
    m = k = 64
    rows, cols, vals = rand_coo(m, k, 500, seed=9)
    plan = PT.make_plan(rows, cols, vals, (m, k), CFG,
                        PT.PlanSpec("single", 1, "balanced"))
    with pytest.raises(ValueError, match="re-encode"):
        PT.plan_apply_delta(plan, np.array([0]), np.array([0]),
                            np.array([1.0], np.float32))

    reg = MatrixRegistry(config=CFG, backend="xla")
    mid = reg.put(rows, cols, vals, (m, k),
                  spec=PT.PlanSpec("single", 1, "balanced"))
    up_r = np.array([1, 2, 3]); up_c = np.array([4, 5, 6])
    up_v = np.array([2.0, -1.0, 0.5], np.float32)
    reg.update(mid, up_r, up_c, up_v)
    dense = dense_of(rows, cols, vals, (m, k))
    dense[up_r, up_c] = up_v                 # updates overwrite
    x = np.random.default_rng(10).normal(size=k).astype(np.float32)
    np.testing.assert_allclose(np.asarray(reg.get(mid).matvec(x)),
                               dense @ x, atol=1e-3, rtol=1e-3)


def test_mesh_repartition_preserves_lane_assign():
    rows, cols, vals = rand_coo(64, 64, 400, seed=11)
    plan = PT.make_plan(rows, cols, vals, (64, 64), CFG,
                        PT.PlanSpec("row", 2, "balanced"))
    assert plan.spec.lane_assign == "balanced"
    spec2 = PT.PlanSpec("row", 4, plan.spec.lane_assign)
    plan2 = PT.make_plan(rows, cols, vals, (64, 64), CFG, spec2)
    assert plan2.spec.lane_assign == "balanced"
    assert plan2.row_perm is not None
