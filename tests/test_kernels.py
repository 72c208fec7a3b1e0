"""Pallas kernel vs pure-jnp oracle: shape/density/config sweeps.

Every sweep asserts allclose against ref.py (the COO oracle) — the
requirement for kernels/ in this framework.  Hypothesis property tests live
in ``test_kernels_properties.py`` (skipped without ``hypothesis``).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import format as F
from repro.core.spmv import SerpensSpMV, from_dense
from repro.kernels import ops
from repro.kernels.ref import spmv_coo_ref, spmm_coo_ref, spmv_dense_ref


def build(m, k, nnz, cfg, seed=0):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m, nnz)
    cols = rng.integers(0, k, nnz)
    vals = rng.normal(size=nnz).astype(np.float32)
    x = rng.normal(size=k).astype(np.float32)
    return rows, cols, vals, x


CFGS = [
    F.SerpensConfig(segment_width=64, lanes=8, sublanes=4, raw_window=4),
    F.SerpensConfig(segment_width=128, lanes=16, sublanes=8, raw_window=8,
                    tiles_per_chunk=2),
    F.SerpensConfig(segment_width=8192, lanes=128, sublanes=8,
                    raw_window=8),  # paper geometry
]


def test_auto_backend_is_xla():
    """Until a Pallas kernel lowers for the chip, "auto" is the XLA stream
    executor on every platform."""
    assert ops.resolve_backend() == ops.resolve_backend("auto") == "xla"
    assert ops.resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown backend"):
        ops.resolve_backend("mosaic")


def test_pallas_kernel_compiles_unless_interpretation_is_asked_for():
    """The kernels' default is a real compile: off the TPU that is refused
    loudly instead of quietly falling back to the interpreter."""
    from repro.kernels import serpens_spmv as K
    cfg = CFGS[0]
    rows, cols, vals, x = build(40, 120, 300, cfg, seed=21)
    sm = F.encode(rows, cols, vals, (40, 120), cfg)
    args = (jnp.asarray(sm.idx), jnp.asarray(sm.val),
            jnp.asarray(sm.seg_ids),
            jnp.asarray(np.pad(x, (0, sm.num_segments * 64 - 120))
                        .reshape(-1, 64)))
    kw = dict(num_rows_padded=sm.padded_rows, segment_width=64)
    got = K.spmv_pallas(*args, **kw, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:40],
                               spmv_coo_ref(rows, cols, vals, x, 40),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(Exception, match="(?i)interpret"):
        K.spmv_pallas(*args, **kw)


@pytest.mark.parametrize("cfg", CFGS)
@pytest.mark.parametrize("m,k,nnz", [(100, 130, 700), (37, 211, 900),
                                     (256, 64, 64), (512, 4096, 3000)])
def test_pallas_matches_oracle(cfg, m, k, nnz):
    rows, cols, vals, x = build(m, k, nnz, cfg, seed=m + nnz)
    op = SerpensSpMV(rows, cols, vals, (m, k), cfg)
    ref = spmv_coo_ref(jnp.asarray(rows), jnp.asarray(cols),
                       jnp.asarray(vals), jnp.asarray(x), m)
    got = op.matvec(x, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cfg", CFGS[:2])
def test_xla_stream_matches_oracle(cfg):
    rows, cols, vals, x = build(90, 300, 1200, cfg, seed=5)
    op = SerpensSpMV(rows, cols, vals, (90, 300), cfg)
    ref = spmv_coo_ref(jnp.asarray(rows), jnp.asarray(cols),
                       jnp.asarray(vals), jnp.asarray(x), 90)
    got = op.matvec(x, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("density", [0.001, 0.01, 0.1, 0.5])
def test_density_sweep(density):
    m = k = 128
    nnz = max(1, int(m * k * density))
    cfg = CFGS[0]
    rows, cols, vals, x = build(m, k, nnz, cfg, seed=int(density * 1e4))
    op = SerpensSpMV(rows, cols, vals, (m, k), cfg)
    ref = spmv_coo_ref(jnp.asarray(rows), jnp.asarray(cols),
                       jnp.asarray(vals), jnp.asarray(x), m)
    for backend in ("pallas", "xla"):
        got = op.matvec(x, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_x_dtype(xdtype):
    """The engine accepts/casts non-f32 inputs (accumulation stays f32)."""
    rows, cols, vals, x = build(64, 64, 256, CFGS[0], seed=9)
    op = SerpensSpMV(rows, cols, vals, (64, 64), CFGS[0])
    got = op.matvec(jnp.asarray(x, xdtype), backend="pallas")
    ref = spmv_coo_ref(jnp.asarray(rows), jnp.asarray(cols),
                       jnp.asarray(vals),
                       jnp.asarray(x, xdtype).astype(jnp.float32), 64)
    tol = 1e-5 if xdtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_spmm_matches_oracle():
    rows, cols, vals, _ = build(70, 90, 500, CFGS[0], seed=11)
    rng = np.random.default_rng(12)
    xm = rng.normal(size=(90, 6)).astype(np.float32)
    op = SerpensSpMV(rows, cols, vals, (70, 90), CFGS[0])
    ref = spmm_coo_ref(jnp.asarray(rows), jnp.asarray(cols),
                       jnp.asarray(vals), jnp.asarray(xm), 70)
    got = op.matmat(xm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_alpha_beta_epilogue():
    rows, cols, vals, x = build(40, 50, 200, CFGS[0], seed=13)
    y = np.random.default_rng(14).normal(size=40).astype(np.float32)
    op = SerpensSpMV(rows, cols, vals, (40, 50), CFGS[0])
    ref = spmv_coo_ref(jnp.asarray(rows), jnp.asarray(cols),
                       jnp.asarray(vals), jnp.asarray(x), 40,
                       alpha=-1.5, beta=0.25, y=jnp.asarray(y))
    got = op(x, alpha=-1.5, beta=0.25, y=y, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


class TestInputValidation:
    """Wrong-length x must fail fast with a clear message (not deep in
    ``ops.pad_x`` with a negative pad width)."""

    @pytest.fixture()
    def op(self):
        rows, cols, vals, _ = build(40, 50, 200, CFGS[0], seed=13)
        return SerpensSpMV(rows, cols, vals, (40, 50), CFGS[0])

    @pytest.mark.parametrize("bad_len", [0, 49, 51, 500])
    def test_matvec_rejects_wrong_length(self, op, bad_len):
        with pytest.raises(ValueError, match="K=50"):
            op.matvec(np.zeros(bad_len, np.float32))

    def test_call_rejects_wrong_length(self, op):
        with pytest.raises(ValueError, match="K=50"):
            op(np.zeros(49, np.float32))

    @pytest.mark.parametrize("bad_len", [49, 51])
    def test_matmat_rejects_wrong_leading_dim(self, op, bad_len):
        with pytest.raises(ValueError, match="K=50"):
            op.matmat(np.zeros((bad_len, 3), np.float32))

    def test_matmat_rejects_non_2d(self, op):
        with pytest.raises(ValueError, match=r"\(K, N\)"):
            op.matmat(np.zeros((50,), np.float32))

    def test_matvec_rejects_2d(self, op):
        with pytest.raises(ValueError, match="1-D"):
            op.matvec(np.zeros((50, 3), np.float32))

    def test_valid_shapes_still_pass(self, op):
        assert op.matvec(np.zeros(50, np.float32)).shape == (40,)
        assert op.matmat(np.zeros((50, 2), np.float32)).shape == (40, 2)


class TestMalformedStreamAsserts:
    """spmv_pallas and spmm_pallas must reject inconsistent stream metadata
    loudly (a wrong seg_ids length would silently mis-index x segments)."""

    @pytest.fixture()
    def stream(self):
        from repro.kernels import serpens_spmv as K
        cfg = F.SerpensConfig(segment_width=64, lanes=8, sublanes=4,
                              raw_window=4, tiles_per_chunk=2)
        rows, cols, vals, _ = build(40, 120, 300, cfg, seed=15)
        sm = F.encode(rows, cols, vals, (40, 120), cfg)
        x2d = np.zeros((sm.num_segments, 64), np.float32)
        x3d = np.zeros((sm.num_segments, 64, 3), np.float32)
        return K, cfg, sm, x2d, x3d

    def test_spmv_rejects_bad_seg_ids(self, stream):
        K, cfg, sm, x2d, _ = stream
        with pytest.raises(ValueError, match="seg_ids"):
            K.spmv_pallas(jnp.asarray(sm.idx), jnp.asarray(sm.val),
                          jnp.asarray(sm.seg_ids[:-1]), jnp.asarray(x2d),
                          num_rows_padded=sm.padded_rows,
                          segment_width=64, tiles_per_chunk=2)

    def test_spmm_rejects_bad_seg_ids(self, stream):
        K, cfg, sm, _, x3d = stream
        chunk_seg = sm.seg_ids[::cfg.tiles_per_chunk]
        with pytest.raises(ValueError, match="seg_ids"):
            K.spmm_pallas(jnp.asarray(sm.idx), jnp.asarray(sm.val),
                          jnp.asarray(np.append(chunk_seg, 0)),
                          jnp.asarray(x3d),
                          num_rows_padded=sm.padded_rows,
                          segment_width=64, tiles_per_chunk=2)

    def test_spmm_rejects_ragged_chunks(self, stream):
        K, cfg, sm, _, x3d = stream
        chunk_seg = sm.seg_ids[::cfg.tiles_per_chunk]
        with pytest.raises(ValueError, match="tiles_per_chunk"):
            K.spmm_pallas(jnp.asarray(sm.idx[:-1]), jnp.asarray(sm.val[:-1]),
                          jnp.asarray(chunk_seg), jnp.asarray(x3d),
                          num_rows_padded=sm.padded_rows,
                          segment_width=64, tiles_per_chunk=2)


class TestFlashAttention:
    """Pallas flash-attention kernel vs pure-jnp oracle (§Perf A6)."""

    @staticmethod
    def _ref(q, k, v, causal):
        dh = q.shape[-1]
        s = jnp.einsum("bckgd,bskd->bkgcs", q, k).astype(jnp.float32) \
            * dh ** -0.5
        if causal:
            m = (jnp.arange(k.shape[1])[None, :]
                 <= jnp.arange(q.shape[1])[:, None])
            s = jnp.where(m[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bkgcs,bskd->bckgd", p.astype(v.dtype), v)

    @pytest.mark.parametrize(
        "b,s,kv,g,dh,dv,causal,qb,kb",
        [(2, 64, 2, 3, 16, 16, True, 16, 32),
         (1, 100, 1, 4, 32, 24, True, 32, 16),   # MLA-style dv != dh
         (2, 80, 2, 1, 16, 16, False, 16, 32),
         (1, 33, 2, 2, 8, 8, True, 8, 8)])       # ragged blocks
    def test_matches_oracle(self, b, s, kv, g, dh, dv, causal, qb, kb):
        from repro.kernels.flash_attention import flash_attention
        rng = np.random.default_rng(b * s + dh)
        q = jnp.asarray(rng.normal(size=(b, s, kv, g, dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, kv, dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, kv, dv)), jnp.float32)
        got = flash_attention(q, k, v, causal=causal, q_block=qb,
                              kv_block=kb, interpret=True)
        want = self._ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_model_attention(self):
        """Kernel == the model's chunked_attention (same math)."""
        from repro.kernels.flash_attention import flash_attention
        from repro.models.attention import chunked_attention
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.normal(size=(2, 48, 2, 2, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 48, 2, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 48, 2, 16)), jnp.float32)
        a = flash_attention(q, k, v, causal=True, q_block=16, kv_block=16,
                            interpret=True)
        b = chunked_attention(q, k, v, causal=True, chunk=16, kv_block=16)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    def test_traffic_model_is_linear_in_seq(self):
        from repro.kernels.flash_attention import traffic_bytes
        t1 = traffic_bytes(1, 4096, 4096, 8, 5, 128, 128)
        t2 = traffic_bytes(1, 8192, 8192, 8, 5, 128, 128)
        assert t2 < 4.2 * t1   # ~quadratic only via nq·KV re-reads


@pytest.mark.parametrize("n", [1, 4, 9])
def test_spmm_pallas_matches_oracle(n):
    """Pallas SpMM kernel (multi-vector Serpens) vs COO oracle."""
    rows, cols, vals, _ = build(80, 150, 600, CFGS[0], seed=21 + n)
    rng = np.random.default_rng(22)
    xm = rng.normal(size=(150, n)).astype(np.float32)
    op = SerpensSpMV(rows, cols, vals, (80, 150), CFGS[0])
    ref = spmm_coo_ref(jnp.asarray(rows), jnp.asarray(cols),
                       jnp.asarray(vals), jnp.asarray(xm), 80)
    got = op.matmat(xm, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_spmm_pallas_with_spill():
    rows = np.concatenate([np.zeros(120, np.int64),
                           np.arange(60, dtype=np.int64)])
    cols = np.concatenate([np.arange(120, dtype=np.int64) % 64,
                           np.arange(60, dtype=np.int64)])
    vals = np.random.default_rng(5).normal(size=180).astype(np.float32)
    cfg = F.SerpensConfig(segment_width=64, lanes=8, sublanes=4,
                          raw_window=2, spill_hot_rows=True,
                          lane_balance=1.2)
    xm = np.random.default_rng(6).normal(size=(64, 3)).astype(np.float32)
    op = SerpensSpMV(rows, cols, vals, (64, 64), cfg)
    ref = spmm_coo_ref(jnp.asarray(rows), jnp.asarray(cols),
                       jnp.asarray(vals), jnp.asarray(xm), 64)
    got = op.matmat(xm, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
