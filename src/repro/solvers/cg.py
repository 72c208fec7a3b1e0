"""Conjugate gradient on a Serpens-resident SPD matrix.

The scientific-solver workload (the paper's FEM/circuit matrices G2/G4/G5):
solve A·x = b with one SpMV per iteration, the whole loop compiled as a
single ``jax.lax.while_loop`` so the A-stream is the only per-iteration
off-chip traffic.

With ``fused`` (default ``"auto"``) the iteration's vector algebra —
``alpha``/``beta`` dots, the three axpys — runs as a fused epilogue inside
the SpMV kernel's output tile loop (:meth:`SerpensOperator.matvec_fused`),
so each iteration is ONE stream dispatch doing matrix *and* vector work;
the state vectors stay in the kernel's (R, LANES) accumulator layout
across iterations (a pure reshape of the flat vectors).  Plans that
cannot fuse (multi-shard, mesh-bound, or aux-spill) fall back to the
classic two-phase body automatically.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from repro.solvers import precision


@dataclasses.dataclass
class CGResult:
    x: jnp.ndarray
    iterations: int
    residual: float          # ‖b − A·x‖₂ (estimate carried by the recursion)
    converged: bool
    fused: bool = False      # iterations ran with the in-kernel epilogue
    tol_effective: float = 0.0   # tol after the value-dtype floor clamp


def _cg_epilogue(ap2, sol2, r2, p2, rs11):
    """One CG iteration's vector work, fused against the fresh ``A·p``
    accumulator (all arrays in (R, LANES) layout; padded rows are zero in
    every operand, so the dots are exact).  Runs inside the kernel's last
    grid step on the Pallas backend."""
    rs = rs11[0, 0]
    denom = jnp.sum(p2 * ap2)
    alpha = rs / jnp.where(denom != 0, denom, 1e-30)
    sol_new = sol2 + alpha * p2
    r_new = r2 - alpha * ap2
    rs_new = jnp.sum(r_new * r_new)
    beta = rs_new / jnp.where(rs != 0, rs, 1e-30)
    p_new = r_new + beta * p2
    return sol_new, r_new, p_new, rs_new.reshape(1, 1)


def _resolve_fused(op, fused):
    if fused == "auto":
        return bool(getattr(op, "supports_fused_epilogue", False))
    if fused and not op.supports_fused_epilogue:
        raise ValueError(
            "fused=True but the operator cannot fuse (multi-shard, "
            "mesh-bound, or aux-spill plan); use fused='auto' to fall "
            "back automatically")
    return bool(fused)


def conjugate_gradient(op, b, x0=None, tol: float = 1e-6,
                       max_iters: int | None = None,
                       backend: str | None = None,
                       mesh=None, axis: str | None = None,
                       fused="auto") -> CGResult:
    """Solve ``A x = b`` for symmetric positive-definite A.

    Stops when ``‖r‖₂ <= tol * ‖b‖₂`` (relative residual) or after
    ``max_iters`` (default: n, CG's exact-arithmetic bound).  ``tol`` is
    clamped to the operator's value-dtype precision floor
    (:mod:`repro.solvers.precision`) — a bf16 stream cannot resolve
    residuals below ~2^-6 of ‖b‖; the clamp warns and the result records
    ``tol_effective``.  With ``mesh``/``axis`` the whole solve runs over
    the channel-shard plan (which disables fusion).
    """
    if mesh is not None:
        op = op.with_mesh(mesh, axis)
    m, k = op.shape
    if m != k:
        raise ValueError(f"CG needs a square (SPD) matrix, got {op.shape}")
    b = jnp.asarray(b, jnp.float32)
    if b.shape != (m,):
        raise ValueError(f"b has shape {b.shape}; expected ({m},)")
    if max_iters is None:
        max_iters = m
    use_fused = _resolve_fused(op, fused)
    tol_eff, _ = precision.effective_tol(
        tol, getattr(op, "value_dtype", "float32"))

    with obs.span("conjugate-gradient", cat="solver", n=m,
                  fused=use_fused) as sp:
        d0 = ops.trace_dispatch_count()
        with obs.span("solver-init", cat="solver"):
            x_init = (jnp.zeros((m,), jnp.float32) if x0 is None
                      else jnp.asarray(x0, jnp.float32))
            b_norm = jnp.linalg.norm(b)
            stop = tol_eff * jnp.maximum(b_norm, 1e-30)
            r_init = b - op.matvec(x_init, backend=backend)
            rs_init = jnp.dot(r_init, r_init)
        solve = _solve_fused if use_fused else _solve_unfused
        with obs.span("solver-launch", cat="solver"):
            x, r, rs, iters = solve(op, x_init, r_init, rs_init, stop,
                                    max_iters, backend)
        with obs.span("solver-wait", cat="solver"):
            res = float(jnp.sqrt(rs))      # blocks until the solve finishes
        sp.args.update(iterations=int(iters), residual=res,
                       stream_dispatches=ops.trace_dispatch_count() - d0)
    return CGResult(x=x, iterations=int(iters), residual=res,
                    converged=res <= float(stop), fused=use_fused,
                    tol_effective=tol_eff)


def _solve_unfused(op, x_init, r_init, rs_init, stop, max_iters, backend):
    def cond(state):
        _, _, _, rs, it = state
        return (jnp.sqrt(rs) > stop) & (it < max_iters)

    def body(state):
        x, r, p, rs, it = state
        ap = op.matvec(p, backend=backend)
        denom = jnp.dot(p, ap)
        alpha = rs / jnp.where(denom != 0, denom, 1e-30)
        x_new = x + alpha * p
        r_new = r - alpha * ap
        rs_new = jnp.dot(r_new, r_new)
        beta = rs_new / jnp.where(rs != 0, rs, 1e-30)
        p_new = r_new + beta * p
        return x_new, r_new, p_new, rs_new, it + 1

    x, r, _, rs, iters = jax.lax.while_loop(
        cond, body, (x_init, r_init, r_init, rs_init, jnp.int32(0)))
    return x, r, rs, iters


def _solve_fused(op, x_init, r_init, rs_init, stop, max_iters, backend):
    """The whole iteration as ONE stream pass: state rides in (R, LANES)
    accumulator layout, the vector algebra is :func:`_cg_epilogue` inside
    the kernel."""
    def cond(state):
        _, _, _, rs11, it = state
        return (jnp.sqrt(rs11[0, 0]) > stop) & (it < max_iters)

    def body(state):
        sol2, r2, p2, rs11, it = state
        _, (sol_n, r_n, p_n, rs_n) = op.matvec_fused(
            op.from_acc_layout(p2), _cg_epilogue,
            extras=(sol2, r2, p2, rs11), backend=backend)
        return sol_n, r_n, p_n, rs_n, it + 1

    sol2, r2, _, rs11, iters = jax.lax.while_loop(
        cond, body,
        (op.to_acc_layout(x_init), op.to_acc_layout(r_init),
         op.to_acc_layout(r_init), rs_init.reshape(1, 1), jnp.int32(0)))
    return (op.from_acc_layout(sol2), op.from_acc_layout(r2),
            rs11[0, 0], iters)
