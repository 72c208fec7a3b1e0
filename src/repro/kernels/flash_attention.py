"""Pallas TPU flash-attention kernel (§Perf A6).

The LM roofline (EXPERIMENTS.md) shows every train/prefill cell
memory-bound on attention-score traffic: the pure-JAX chunked attention
(models/attention.py) streams K/V through XLA scans whose per-block
(C × KVb) f32 score tensors round-trip HBM.  This kernel keeps the running
(m, l, acc) online-softmax state in VMEM scratch across the innermost grid
dimension, so per layer the only HBM traffic is Q/K/V read once + O
written once:

    traffic_flash  = (3·S·H·dh + S·H·dv) · bytes        per (batch, head)
    traffic_xla    ≈ 2-4 · S² · 4 B                      per (batch, head)

At S = 32k that is a ~200× reduction of the attention term (napkin in
EXPERIMENTS.md §Perf A6).

Grid: (B·KV·G, nq, nkv) with ``dimension_semantics`` (parallel, parallel,
arbitrary) — the kv dimension is the sequential accumulation axis, exactly
the Serpens output-stationary pattern reused for attention.

Validated in interpret mode against the pure-jnp oracle for causal /
non-causal, GQA grouping, and MLA-style dv ≠ dh (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal, sk_real, kv_block, q_block, scale):
    ci = pl.program_id(1)
    j = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                        # (Cq, dh)
    k = k_ref[0]                        # (Ckv, dh)
    v = v_ref[0]                        # (Ckv, dv)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = ci * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                   s.shape, 0)
    kpos = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < sk_real
    if causal:
        mask = mask & (kpos <= qpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jnp.dot(p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(j == nkv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_block", "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal=True, q_block=512, kv_block=1024,
                    interpret=False):
    """q: (B, Sq, KV, G, dh); k: (B, Sk, KV, dh); v: (B, Sk, KV, dv).

    Returns (B, Sq, KV, G, dv).  Self-attention layout (q_offset 0);
    sequences are padded to block multiples internally.
    """
    b, sq, kvh, g, dh = q.shape
    sk = k.shape[1]
    dv = v.shape[-1]
    scale = dh ** -0.5

    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    qpad = (-sq) % q_block
    kpad = (-sk) % kv_block
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0), (0, 0)))
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0), (0, 0)))
    nq = (sq + qpad) // q_block
    nkv = (sk + kpad) // kv_block

    # collapse (B, KV, G) into one parallel "head" axis
    qh = q.transpose(0, 2, 3, 1, 4).reshape(b * kvh * g, sq + qpad, dh)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(
        b * kvh * g, sk + kpad, dh)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(
        b * kvh * g, sk + kpad, dv)

    kernel = functools.partial(
        _flash_kernel, causal=causal, sk_real=sk, kv_block=kv_block,
        q_block=q_block, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * kvh * g, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, q_block, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, kv_block, dh), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, kv_block, dv), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, dv), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh * g, sq + qpad, dv),
                                       q.dtype),
        scratch_shapes=[
            pl.ScratchShape((q_block,), jnp.float32)
            if hasattr(pl, "ScratchShape") else
            _scratch((q_block,)),
            _scratch((q_block,)),
            _scratch((q_block, dv)),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    out = out.reshape(b, kvh, g, sq + qpad, dv).transpose(0, 3, 1, 2, 4)
    return out[:, :sq]


def _scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def traffic_bytes(b, sq, sk, kvh, g, dh, dv, dtype_bytes=2):
    """Analytic HBM traffic of one flash-attention call (the §Perf A6
    napkin): Q/K/V read once, O written once; K/V re-read per q-block row
    of the grid is avoided by the (parallel, parallel, arbitrary) order —
    conservatively count K/V once per q-block."""
    nq = -(-sq // 512)
    q_bytes = b * sq * kvh * g * dh * dtype_bytes
    kv_bytes = b * sk * kvh * (dh + dv) * dtype_bytes * nq
    o_bytes = b * sq * kvh * g * dv * dtype_bytes
    return q_bytes + kv_bytes + o_bytes
