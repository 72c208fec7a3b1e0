"""Power iteration / PageRank on a Serpens-resident matrix.

The paper's graph-analytics use case (Sec. 1: "graph processing ... PageRank")
as a *workload*, not an example script: the entire solve is one
``jax.lax.while_loop`` whose body is the Serpens SpMV, so A streams from HBM
once per iteration and nothing bounces through the host until convergence.

With ``fused`` (default ``"auto"``) each iteration's vector work — the
teleport/dangling-mass redistribution and L1 delta (pagerank) or the
Rayleigh quotient, residual, and normalize (power iteration) — runs as a
fused epilogue inside the SpMV kernel's output tile loop, so one stream
dispatch per iteration does matrix *and* vector work; see
:meth:`SerpensOperator.matvec_fused`.  Plans that cannot fuse fall back
to the two-phase body automatically.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from repro.solvers import precision
from repro.solvers.cg import _resolve_fused


@dataclasses.dataclass
class PowerResult:
    x: jnp.ndarray          # final vector (PageRank: probability vector)
    iterations: int
    residual: float         # L1 delta (pagerank) / eigen-residual norm
    eigenvalue: float | None = None  # power_iteration only
    converged: bool = False
    fused: bool = False     # iterations ran with the in-kernel epilogue
    tol_effective: float = 0.0  # tol after the value-dtype floor clamp


def _square(op):
    m, k = op.shape
    if m != k:
        raise ValueError(f"solver needs a square matrix, got {op.shape}")
    return m


def _bind(op, mesh, axis):
    """Rebind the operator's channel-shard plan to a mesh axis so every
    per-iteration SpMV in the while_loop runs sharded."""
    if mesh is None:
        return op
    return op.with_mesh(mesh, axis)


def _pagerank_epilogue(acc2, r2, mask2, consts):
    """One PageRank step fused against the fresh ``A·r`` accumulator.

    ``mask2`` is 1.0 on real rows, 0.0 on the accumulator's padding rows —
    the uniform teleport mass must not leak into padding (the unfused body
    never sees padded rows because matvec slices ``[:m]``).  ``consts`` is
    ``[[damping, n]]``.
    """
    damping, n = consts[0, 0], consts[0, 1]
    link = damping * acc2              # padded rows of acc2 are zero
    r_new = (link + (1.0 - jnp.sum(link)) / n) * mask2
    delta = jnp.sum(jnp.abs(r_new - r2))
    return r_new, delta.reshape(1, 1)


def pagerank(op, damping: float = 0.85, tol: float = 1e-9,
             max_iters: int = 100, r0=None, backend: str | None = None,
             mesh=None, axis: str | None = None,
             fused="auto") -> PowerResult:
    """PageRank: r ← d·A·r + (1-d+dangling mass)/n, to an L1 tolerance.

    ``op`` is a :class:`~repro.core.spmv.SerpensSpMV` whose columns are
    out-degree-normalized (column-substochastic; dangling columns may be
    all-zero — their mass is redistributed uniformly each step, keeping r a
    probability vector).  ``tol`` is clamped to the operator's value-dtype
    precision floor (bf16 streams; see :mod:`repro.solvers.precision`).
    """
    op = _bind(op, mesh, axis)
    n = _square(op)
    use_fused = _resolve_fused(op, fused)
    tol_eff, _ = precision.effective_tol(
        tol, getattr(op, "value_dtype", "float32"))

    with obs.span("pagerank", cat="solver", n=n, damping=float(damping),
                  fused=use_fused) as sp:
        d0 = ops.trace_dispatch_count()
        with obs.span("solver-init", cat="solver"):
            r_init = (jnp.full((n,), 1.0 / n, jnp.float32) if r0 is None
                      else jnp.asarray(r0, jnp.float32))
            if use_fused:
                mask2 = op.to_acc_layout(jnp.ones((n,), jnp.float32))
                consts = jnp.array([[damping, n]], jnp.float32)
                state0 = (op.to_acc_layout(r_init),
                          jnp.full((1, 1), jnp.inf, jnp.float32),
                          jnp.int32(0))
            else:
                state0 = (r_init, jnp.float32(jnp.inf), jnp.int32(0))
        if use_fused:
            def cond(state):
                _, delta11, it = state
                return (delta11[0, 0] > tol_eff) & (it < max_iters)

            def body(state):
                r2, _, it = state
                _, (r_new, delta11) = op.matvec_fused(
                    op.from_acc_layout(r2), _pagerank_epilogue,
                    extras=(r2, mask2, consts), backend=backend)
                return r_new, delta11, it + 1

            with obs.span("solver-launch", cat="solver"):
                r2, delta11, iters = jax.lax.while_loop(cond, body, state0)
                r, delta = op.from_acc_layout(r2), delta11[0, 0]
        else:
            def cond(state):
                _, delta, it = state
                return (delta > tol_eff) & (it < max_iters)

            def body(state):
                r, _, it = state
                link = damping * op.matvec(r, backend=backend)
                # teleport + dangling-node mass: whatever probability the
                # (sub)stochastic step lost comes back uniformly.
                r_new = link + (1.0 - jnp.sum(link)) / n
                delta = jnp.sum(jnp.abs(r_new - r))
                return r_new, delta, it + 1

            with obs.span("solver-launch", cat="solver"):
                r, delta, iters = jax.lax.while_loop(cond, body, state0)
        with obs.span("solver-wait", cat="solver"):
            delta = float(delta)       # blocks until the solve finishes
        sp.args.update(iterations=int(iters), residual=delta,
                       stream_dispatches=ops.trace_dispatch_count() - d0)
    return PowerResult(x=r, iterations=int(iters), residual=delta,
                       converged=delta <= tol_eff, fused=use_fused,
                       tol_effective=tol_eff)


def _power_epilogue(av2, v2):
    """One power-iteration step fused against the fresh ``A·v``: Rayleigh
    quotient, eigen-residual, and the normalize — padded rows are zero in
    both operands, so every reduction is exact."""
    lam = jnp.sum(v2 * av2)            # Rayleigh quotient (v unit-norm)
    res = jnp.sqrt(jnp.sum((av2 - lam * v2) ** 2))
    nrm = jnp.sqrt(jnp.sum(av2 * av2))
    v_new = jnp.where(nrm > 0, av2 / jnp.maximum(nrm, 1e-30), v2)
    return v_new, lam.reshape(1, 1), res.reshape(1, 1)


def power_iteration(op, tol: float = 1e-6, max_iters: int = 200,
                    v0=None, backend: str | None = None,
                    mesh=None, axis: str | None = None,
                    fused="auto") -> PowerResult:
    """Dominant eigenpair of a square A by normalized power iteration.

    Converges for matrices with a simple dominant eigenvalue; the residual
    is ``‖A·v − λ·v‖₂`` with v unit-norm.  ``tol`` is clamped to the
    operator's value-dtype precision floor (bf16 streams).
    """
    op = _bind(op, mesh, axis)
    n = _square(op)
    use_fused = _resolve_fused(op, fused)
    tol_eff, _ = precision.effective_tol(
        tol, getattr(op, "value_dtype", "float32"))

    with obs.span("power-iteration", cat="solver", n=n,
                  fused=use_fused) as sp:
        d0 = ops.trace_dispatch_count()
        with obs.span("solver-init", cat="solver"):
            if v0 is None:
                v_init = jnp.ones((n,), jnp.float32) / jnp.sqrt(n)
            else:
                v_init = jnp.asarray(v0, jnp.float32)
                v_init = v_init / jnp.linalg.norm(v_init)
            if use_fused:
                state0 = (op.to_acc_layout(v_init),
                          jnp.zeros((1, 1), jnp.float32),
                          jnp.full((1, 1), jnp.inf, jnp.float32),
                          jnp.int32(0))
            else:
                state0 = (v_init, jnp.float32(0.0), jnp.float32(jnp.inf),
                          jnp.int32(0))
        if use_fused:
            def cond(state):
                _, _, res11, it = state
                return (res11[0, 0] > tol_eff) & (it < max_iters)

            def body(state):
                v2, _, _, it = state
                _, (v_new, lam11, res11) = op.matvec_fused(
                    op.from_acc_layout(v2), _power_epilogue,
                    extras=(v2,), backend=backend)
                return v_new, lam11, res11, it + 1

            with obs.span("solver-launch", cat="solver"):
                v2, lam11, res11, iters = jax.lax.while_loop(
                    cond, body, state0)
                v, lam, res = (op.from_acc_layout(v2), lam11[0, 0],
                               res11[0, 0])
        else:
            def cond(state):
                _, _, res, it = state
                return (res > tol_eff) & (it < max_iters)

            def body(state):
                v, _, _, it = state
                av = op.matvec(v, backend=backend)
                lam = jnp.dot(v, av)             # Rayleigh quotient
                res = jnp.linalg.norm(av - lam * v)
                nrm = jnp.linalg.norm(av)
                v_new = jnp.where(nrm > 0, av / jnp.maximum(nrm, 1e-30), v)
                return v_new, lam, res, it + 1

            with obs.span("solver-launch", cat="solver"):
                v, lam, res, iters = jax.lax.while_loop(cond, body, state0)
        with obs.span("solver-wait", cat="solver"):
            res = float(res)           # blocks until the solve finishes
        sp.args.update(iterations=int(iters), residual=res,
                       stream_dispatches=ops.trace_dispatch_count() - d0)
    return PowerResult(x=v, iterations=int(iters), residual=res,
                       eigenvalue=float(lam), converged=res <= tol_eff,
                       fused=use_fused, tol_effective=tol_eff)
