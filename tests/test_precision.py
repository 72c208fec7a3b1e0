"""Mixed-precision value streams (bf16) + fused solver epilogues.

The bf16 stream's entire precision loss happens once, at encode time:
``Â = A + E``, ``|E| <= eps·|A|`` elementwise with ``eps = 2^-8``
(accumulation stays fp32 on every backend).  That gives an *analytic*
SpMV error bound — ``|Âx − Ax| <= eps·(|A| @ |x|)`` — which this suite
asserts across matrix families, spill configs and plan geometries.  The
rest covers the encode pipeline's bit-identity per dtype (cold ==
incremental splice == parallel encode), the operator/service dtype
boundary (silent promotion fixed → explicit TypeError), fused-epilogue
solver parity and its one-stream-pass-per-iteration guarantee, byte
accounting at 6 B/slot, and the solver tolerance floor clamp.
"""
import numpy as np
import pytest

from repro.core import format as F
from repro.core import parallel_encode as penc
from repro.core import partition as P
from repro.core.registry import MatrixRegistry
from repro.core.spmv import SerpensSpMV, from_dense
from repro.data import matrices as M
from repro.kernels import ops
from repro.serve.spmv_service import SpMVService
from repro.solvers import (conjugate_gradient, effective_tol, pagerank,
                           power_iteration, tolerance_floor, value_eps)
from test_format import dense_of, rand_coo
from test_update import (assert_plans_identical, make_delta,
                         post_delta_triples)

CFG = F.SerpensConfig(segment_width=64, lanes=8, sublanes=4, raw_window=4)
SPILL_CFG = F.SerpensConfig(segment_width=32, lanes=4, sublanes=4,
                            raw_window=2, spill_hot_rows=True,
                            lane_balance=1.2)
BF16 = {"value_dtype": "bfloat16"}
EPS_BF16 = 2.0 ** -8


def cfg_at(cfg, dtype):
    import dataclasses
    return dataclasses.replace(cfg, value_dtype=dtype)


def matrix_family(family, seed=0):
    """(rows, cols, vals, shape) for one test matrix family."""
    if family == "power_law":
        n = 96
        r, c, v = M.power_law_graph(n, 700, seed=seed)
        return r, c, v, (n, n)
    if family == "banded":
        n = 80
        r, c, v = M.banded(n, 5, seed=seed)
        return r, c, v, (n, n)
    if family == "uniform":
        r, c, v = M.uniform_random(70, 90, 600, seed=seed)
        return r, c, v, (70, 90)
    raise ValueError(family)


def ops_at_both(rows, cols, vals, shape, cfg, spec=P.PlanSpec(),
                backend="auto"):
    """The same matrix as fp32 and bf16 operators over one geometry."""
    mk = {}
    for dt in ("float32", "bfloat16"):
        plan = P.make_plan(rows, cols, vals, shape, cfg_at(cfg, dt), spec)
        from repro.core.spmv import SerpensOperator
        mk[dt] = SerpensOperator(plan, backend=backend)
    return mk["float32"], mk["bfloat16"]


class TestErrorBound:
    """|y_bf16 − y_fp32| <= eps_bf16 · (|A| @ |x|), elementwise.

    Both operators accumulate fp32 in the identical stream order, so the
    measured difference is purely the encode-time value rounding — the
    analytic bound must hold exactly (tiny atol for the subtraction)."""

    @pytest.mark.parametrize("family", ["power_law", "banded", "uniform"])
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_bound_across_families(self, family, backend):
        rows, cols, vals, shape = matrix_family(family, seed=7)
        op32, op16 = ops_at_both(rows, cols, vals, shape, CFG,
                                 backend=backend)
        rng = np.random.default_rng(11)
        x = rng.normal(size=shape[1]).astype(np.float32)
        y32 = np.asarray(op32.matvec(x), np.float64)
        y16 = np.asarray(op16.matvec(x), np.float64)
        a_abs = np.abs(dense_of(rows, cols, vals, shape)).astype(np.float64)
        bound = EPS_BF16 * (a_abs @ np.abs(x).astype(np.float64))
        assert np.all(np.abs(y16 - y32) <= bound + 1e-5)
        # and the error is real: bf16 differs from fp32 on generic data
        assert np.any(y16 != y32)

    @pytest.mark.parametrize("spec_args", [("single", 1), ("row", 2),
                                           ("row", 3), ("col", 2)])
    def test_bound_across_plan_geometries(self, spec_args):
        rows, cols, vals, shape = matrix_family("power_law", seed=3)
        op32, op16 = ops_at_both(rows, cols, vals, shape, CFG,
                                 spec=P.PlanSpec(*spec_args))
        rng = np.random.default_rng(5)
        x = rng.normal(size=shape[1]).astype(np.float32)
        y32 = np.asarray(op32.matvec(x), np.float64)
        y16 = np.asarray(op16.matvec(x), np.float64)
        a_abs = np.abs(dense_of(rows, cols, vals, shape)).astype(np.float64)
        bound = EPS_BF16 * (a_abs @ np.abs(x).astype(np.float64))
        assert np.all(np.abs(y16 - y32) <= bound + 1e-5)

    def test_bound_with_hot_row_spill(self):
        """Spill plans keep the aux COO side-stream fp32; the bound still
        holds (it is conservative for the spilled entries)."""
        rows, cols, vals, shape = matrix_family("power_law", seed=13)
        op32, op16 = ops_at_both(rows, cols, vals, shape, SPILL_CFG)
        assert op16.plan.n_aux > 0, "family must exercise the spill path"
        rng = np.random.default_rng(17)
        x = rng.normal(size=shape[1]).astype(np.float32)
        y32 = np.asarray(op32.matvec(x), np.float64)
        y16 = np.asarray(op16.matvec(x), np.float64)
        a_abs = np.abs(dense_of(rows, cols, vals, shape)).astype(np.float64)
        bound = EPS_BF16 * (a_abs @ np.abs(x).astype(np.float64))
        assert np.all(np.abs(y16 - y32) <= bound + 1e-5)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_backends_bitwise_agree_per_dtype(self, dtype):
        """xla sums each row along its row-ordered slots, pallas in stream
        order (a scatter-add).  At both stream precisions they agree
        bitwise on rows of at most two products (fp32 addition commutes)
        and elsewhere to the rounding of an fp32 sum of the row's
        products, 2·γ_n·(|Â| @ |x|) for the longest row n."""
        rows, cols, vals, shape = matrix_family("uniform", seed=19)
        plan = P.make_plan(rows, cols, vals, shape, cfg_at(CFG, dtype),
                           P.PlanSpec())
        from repro.core.spmv import SerpensOperator
        op = SerpensOperator(plan, backend="auto")
        x = np.random.default_rng(23).normal(size=shape[1]).astype(
            np.float32)
        y_xla = np.asarray(op.matvec(x, backend="xla"), np.float64)
        y_pallas = np.asarray(op.matvec(x, backend="pallas"), np.float64)
        r, c, v = plan.to_coo()
        a_abs = np.zeros(shape)
        np.add.at(a_abs, (r, c), np.abs(v.astype(np.float64)))
        counts = (a_abs != 0).sum(axis=1)
        np.testing.assert_array_equal(y_xla[counts <= 2],
                                      y_pallas[counts <= 2])
        n = int(counts.max()) + 1
        gamma = n * 2.0 ** -24 / (1 - n * 2.0 ** -24)
        assert np.all(np.abs(y_xla - y_pallas)
                      <= 2 * gamma * (a_abs @ np.abs(x)))


class TestBitIdentityPerDtype:
    """Cold encode == incremental splice == parallel encode, per dtype.

    Rounding to the stream dtype happens exactly once (fp32 master values
    in PreparedCOO, rounded at stream materialization), so every encode
    path must produce byte-identical val arrays."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("mode", ["add", "set", "delete"])
    def test_splice_matches_cold_encode(self, dtype, mode):
        cfg = cfg_at(CFG, dtype)
        rows, cols, vals = rand_coo(96, 120, 700, seed=29, dupes=True)
        rows = np.asarray(rows, np.int64); cols = np.asarray(cols, np.int64)
        prep = F.prepare(rows, cols, vals, (96, 120), cfg)
        plan = P.plan_from_prepared(prep, P.PlanSpec())
        dr, dc, dv = make_delta(rows, cols, 96, 120, 50, seed=31,
                                overlap=20)
        new_plan, _, _ = P.plan_apply_delta(plan, prep, dr, dc, dv,
                                            mode=mode)
        rr, cc, vv = post_delta_triples(rows, cols,
                                        np.asarray(vals, np.float32),
                                        dr, dc, dv, 120, mode)
        cold = P.make_plan(rr, cc, vv, (96, 120), cfg, P.PlanSpec())
        assert str(new_plan.val.dtype) == dtype
        assert_plans_identical(new_plan, cold)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("spec_args", [("single", 1), ("row", 2)])
    def test_parallel_encode_matches_serial(self, dtype, spec_args):
        cfg = cfg_at(CFG, dtype)
        rows, cols, vals = rand_coo(128, 200, 1500, seed=37, dupes=True)
        spec = P.PlanSpec(*spec_args)
        serial = P.make_plan(rows, cols, vals, (128, 200), cfg, spec)
        _, parallel = penc.prepare_and_plan(rows, cols, vals, (128, 200),
                                            cfg, spec, n_workers=2)
        assert str(parallel.val.dtype) == dtype
        assert_plans_identical(parallel, serial)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_reference_encoder_same_rounding(self, dtype):
        """The greedy reference encoder rounds identically: decoded
        multisets match the vectorized encoder's bit-for-bit."""
        cfg = cfg_at(F.SerpensConfig(segment_width=32, lanes=4, sublanes=4,
                                     raw_window=4), dtype)
        rows, cols, vals = rand_coo(40, 60, 250, seed=41, dupes=True)
        sv = F.encode(rows, cols, vals, (40, 60), cfg)
        sr = F.encode_reference(rows, cols, vals, (40, 60), cfg)
        np.testing.assert_array_equal(
            dense_of(*F.decode_to_coo(sv), (40, 60)),
            dense_of(*F.decode_to_coo(sr), (40, 60)))

    def test_bf16_roundtrip_within_eps(self):
        """encode→decode recovers A within one bf16 rounding per entry."""
        rows, cols, vals, shape = matrix_family("banded", seed=43)
        sm = F.encode(rows, cols, vals, shape, cfg_at(CFG, "bfloat16"))
        F.check_invariants(sm)
        got = dense_of(*F.decode_to_coo(sm), shape)
        want = dense_of(rows, cols, vals, shape)
        assert np.all(np.abs(got - want) <= EPS_BF16 * np.abs(want) + 1e-7)


class TestDtypeBoundary:
    """The silent-promotion fix: floating inputs cast to fp32 at the
    operator boundary, non-floating inputs are a TypeError."""

    def setup_method(self):
        rows, cols, vals = rand_coo(32, 48, 200, seed=47)
        self.op = SerpensSpMV(rows, cols, vals, (32, 48), CFG)

    def test_matvec_rejects_int(self):
        with pytest.raises(TypeError, match="floating"):
            self.op.matvec(np.arange(48))

    def test_matmat_rejects_int(self):
        with pytest.raises(TypeError, match="floating"):
            self.op.matmat(np.ones((48, 3), np.int32))

    def test_float64_casts_not_promotes(self):
        y = self.op.matvec(np.ones(48, np.float64))
        assert y.dtype == np.float32

    def test_beta_y_rejects_int(self):
        with pytest.raises(TypeError, match="floating"):
            self.op(np.ones(48, np.float32), beta=1.0,
                    y=np.zeros(32, np.int64))

    def test_service_submit_rejects_int(self):
        rows, cols, vals = rand_coo(24, 30, 120, seed=53)
        reg = MatrixRegistry(config=CFG)
        mid = reg.put(rows, cols, vals, (24, 30))
        svc = SpMVService(reg)
        with pytest.raises(TypeError, match="floating"):
            svc.submit(mid, np.arange(30))
        with pytest.raises(TypeError, match="floating"):
            svc.submit(mid, np.ones(30, np.float32), beta=1.0,
                       y=np.zeros(24, np.int32))


def spd_system(n=48, seed=0, dtype="float32"):
    rng = np.random.default_rng(seed)
    a = np.zeros((n, n), np.float32)
    idx = rng.integers(0, n, (4 * n, 2))
    a[idx[:, 0], idx[:, 1]] = rng.normal(size=4 * n)
    a = (a + a.T) / 2
    a[np.arange(n), np.arange(n)] = np.abs(a).sum(1) + 1.0
    op = from_dense(a, cfg_at(CFG, dtype))
    b = rng.normal(size=n).astype(np.float32)
    return op, a, b


class TestFusedSolvers:
    """fused="auto" epilogue path: parity with the two-phase body, one
    stream dispatch per iteration, and clean fallback/rejection."""

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_cg_fused_matches_unfused(self, backend):
        op, a, b = spd_system(n=40 + (backend == "pallas") * 8, seed=59)
        assert op.supports_fused_epilogue
        rf = conjugate_gradient(op, b, tol=1e-6, fused=True,
                                backend=backend)
        ru = conjugate_gradient(op, b, tol=1e-6, fused=False,
                                backend=backend)
        assert rf.fused and not ru.fused
        assert rf.converged and ru.converged
        np.testing.assert_allclose(np.asarray(rf.x), np.asarray(ru.x),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(rf.x),
                                   np.linalg.solve(a, b), atol=1e-3)

    def test_pagerank_fused_matches_unfused(self):
        n = 88
        rows, cols, vals = M.power_law_graph(n, 600, seed=61)
        vals_n = M.column_normalize(rows, cols, vals, n)
        op = SerpensSpMV(rows, cols, vals_n, (n, n), CFG)
        rf = pagerank(op, tol=1e-7, max_iters=300, fused=True)
        ru = pagerank(op, tol=1e-7, max_iters=300, fused=False)
        assert rf.fused and rf.converged and ru.converged
        np.testing.assert_allclose(np.asarray(rf.x), np.asarray(ru.x),
                                   atol=1e-6)
        assert abs(float(np.asarray(rf.x).sum()) - 1.0) < 1e-3

    def test_power_iteration_fused_matches_unfused(self):
        op, a, _ = spd_system(n=36, seed=67)
        rf = power_iteration(op, tol=1e-6, fused=True)
        ru = power_iteration(op, tol=1e-6, fused=False)
        assert rf.fused and rf.converged
        assert rf.eigenvalue == pytest.approx(ru.eigenvalue, rel=1e-4)
        lam_max = float(np.linalg.eigvalsh(a)[-1])
        assert rf.eigenvalue == pytest.approx(lam_max, rel=1e-3)

    def test_fused_pagerank_is_one_dispatch_per_iteration(self):
        """Acceptance: the fused body issues exactly ONE stream dispatch
        per traced iteration (matrix + vector work in the same pass)."""
        n = 92       # distinct size: no trace-cache hit from other tests
        rows, cols, vals = M.power_law_graph(n, 640, seed=71)
        vals_n = M.column_normalize(rows, cols, vals, n)
        op = SerpensSpMV(rows, cols, vals_n, (n, n), CFG)
        d0 = ops.trace_dispatch_count()
        pagerank(op, tol=1e-6, max_iters=50, fused=True)
        assert ops.trace_dispatch_count() - d0 == 1

    def test_fused_cg_is_init_plus_one_dispatch(self):
        """CG traces two stream passes total: the r0 matvec and the single
        fused pass inside the while_loop body."""
        op, _, b = spd_system(n=52, seed=73)
        d0 = ops.trace_dispatch_count()
        conjugate_gradient(op, b, tol=1e-6, fused=True)
        assert ops.trace_dispatch_count() - d0 == 2

    def test_fused_rejected_on_multi_shard(self):
        rows, cols, vals, shape = matrix_family("uniform", seed=79)
        plan = P.make_plan(rows, cols, vals, (90, 90), CFG,
                           P.PlanSpec("row", 2))
        from repro.core.spmv import SerpensOperator
        op = SerpensOperator(plan)
        assert not op.supports_fused_epilogue
        b = np.ones(90, np.float32)
        with pytest.raises(ValueError, match="fused"):
            conjugate_gradient(op, b, fused=True)
        # auto falls back silently
        res = pagerank(op, max_iters=3, fused="auto")
        assert not res.fused

    def test_acc_layout_roundtrip(self):
        op, _, _ = spd_system(n=50, seed=83)
        v = np.random.default_rng(89).normal(size=50).astype(np.float32)
        back = np.asarray(op.from_acc_layout(op.to_acc_layout(v)))
        np.testing.assert_array_equal(back, v)


class TestToleranceFloor:
    def test_floor_values(self):
        assert tolerance_floor("float32") == 0.0
        assert tolerance_floor("bfloat16") == 4 * 2.0 ** -8
        assert value_eps("bfloat16") == 2.0 ** -8

    def test_clamp_warns_below_floor(self):
        with pytest.warns(UserWarning, match="precision"):
            tol, clamped = effective_tol(1e-9, "bfloat16")
        assert clamped and tol == tolerance_floor("bfloat16")

    def test_no_clamp_for_fp32(self):
        tol, clamped = effective_tol(1e-12, "float32")
        assert not clamped and tol == 1e-12

    def test_cg_clamps_and_still_converges(self):
        op16, a, b = spd_system(n=44, seed=97, dtype="bfloat16")
        op32, _, _ = spd_system(n=44, seed=97)
        with pytest.warns(UserWarning, match="precision"):
            r16 = conjugate_gradient(op16, b, tol=1e-9)
        assert r16.tol_effective == tolerance_floor("bfloat16")
        assert r16.converged
        r32 = conjugate_gradient(op32, b, tol=1e-9)
        # bf16 solve lands within its precision floor of the fp32 answer
        diff = np.linalg.norm(np.asarray(r16.x) - np.asarray(r32.x))
        scale = np.linalg.norm(np.asarray(r32.x))
        assert diff <= r16.tol_effective * scale * 4


class TestByteAccounting:
    """6 B/slot at bf16 everywhere bytes are counted: SerpensMatrix,
    ChannelShardPlan, cost_report, registry budget."""

    def test_stream_bytes_per_slot(self):
        rows, cols, vals, shape = matrix_family("uniform", seed=101)
        for dtype, per_slot in (("float32", 8), ("bfloat16", 6)):
            sm = F.encode(rows, cols, vals, shape, cfg_at(CFG, dtype))
            assert sm.stream_bytes == sm.idx.size * per_slot \
                + 12 * sm.n_aux

    def test_bf16_is_three_quarters_on_spill_free(self):
        rows, cols, vals, shape = matrix_family("banded", seed=103)
        s32 = F.encode(rows, cols, vals, shape, cfg_at(CFG, "float32"))
        s16 = F.encode(rows, cols, vals, shape, cfg_at(CFG, "bfloat16"))
        assert s32.n_aux == 0
        assert s16.stream_bytes * 4 == s32.stream_bytes * 3

    def test_cost_report_carries_dtype(self):
        rows, cols, vals, shape = matrix_family("uniform", seed=107)
        op32, op16 = ops_at_both(rows, cols, vals, shape, CFG)
        r32, r16 = op32.cost_report(), op16.cost_report()
        assert r32["value_dtype"] == "float32" \
            and r32["bytes_per_slot"] == 8
        assert r16["value_dtype"] == "bfloat16" \
            and r16["bytes_per_slot"] == 6
        assert r16["stream_bytes"] < r32["stream_bytes"]
        assert r16["bytes_per_nnz"] < r32["bytes_per_nnz"]

    def test_registry_keys_and_budget_per_dtype(self):
        rows, cols, vals, shape = matrix_family("uniform", seed=109)
        reg = MatrixRegistry(config=CFG)
        k32 = reg.put(rows, cols, vals, shape)
        k16 = reg.put(rows, cols, vals, shape, value_dtype="bfloat16")
        assert k32 != k16                   # dtype is part of the content key
        assert reg.get(k16).value_dtype == "bfloat16"
        assert reg.get(k16).plan.stream_bytes \
            < reg.get(k32).plan.stream_bytes
        # repeat put at the same dtype is a hit, not a re-encode
        h0 = reg.stats.hits
        assert reg.put(rows, cols, vals, shape,
                       value_dtype="bfloat16") == k16
        assert reg.stats.hits == h0 + 1
