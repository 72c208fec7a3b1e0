"""Public SpMV API: ``y = alpha * A @ x + beta * y`` with Serpens-formatted A.

This is the paper's contract (Sec. 1) including the CompY (α, β) epilogue.
Execution is organized around a channel-shard plan
(:mod:`repro.core.partition`): :class:`SerpensOperator` runs *any* plan —
one shard or many, on one device or ``shard_map``'d over a mesh axis,
matvec or matmat, XLA or Pallas — through the single dispatch point
``kernels/ops.run_stream``, with the hot-row aux-spill epilogue applied
uniformly per shard.  :class:`SerpensSpMV` is the classic single-shard
operator as a thin wrapper (preprocessing runs on host, exactly like the
paper's offline format conversion; construct once, apply to many vectors).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import format as sformat
from repro.core import partition as cpart
from repro.kernels import ops


class SerpensOperator:
    """y = α·A·x + β·y for a fixed sparse A under a channel-shard plan.

    With ``mesh``/``axis`` the shards execute in parallel under
    ``shard_map`` (row partition: disjoint accumulators concatenate; col
    partition: partial y's ``psum``).  Without a mesh a multi-shard plan
    executes shard-by-shard on the local device — the same math, used for
    parity tests and single-host channel-scaling sweeps.
    """

    def __init__(self, plan: cpart.ChannelShardPlan, *, mesh=None,
                 axis: str | None = None, backend: str = "auto"):
        if (mesh is None) != (axis is None):
            raise ValueError("mesh and axis must be given together")
        self.plan = plan
        self.config = plan.config
        self.shape = tuple(plan.shape)
        # Resolve "auto" exactly once at bind time: a per-call
        # jax.default_backend() lookup inside jit traces is both overhead
        # and a tracing hazard.  "auto" stays accepted at the API edge
        # (run_stream resolves it for direct callers).
        self.backend = ops.resolve_backend(backend)
        self.mesh = mesh
        self.axis = axis
        # lane_assign="balanced" plans encode row r at virtual row
        # row_perm[r]; the final gather restores caller row order.
        self._row_perm = (None if plan.row_perm is None
                          else jnp.asarray(plan.row_perm))
        cfg = plan.config
        if mesh is not None:
            n = mesh.shape[axis]
            if n != plan.num_shards:
                raise ValueError(
                    f"plan has {plan.num_shards} shards but mesh axis "
                    f"{axis!r} has {n} devices")
            sh = jax.NamedSharding(mesh, P(axis))
            # One length for the stack: the shorter shards end in zero
            # slots.
            length = max(map(ops.row_slots, plan.shards))
            ordered = [ops.row_order(sm, length) for sm in plan.shards]
            self._dev = ops.ShardArrays(
                *(jax.device_put(a, sh) for a in (
                    plan.idx, plan.val,
                    plan.seg_ids[:, ::cfg.tiles_per_chunk],
                    *map(np.stack, zip(*ordered)))))
            self._aux = tuple(jax.device_put(a, sh) for a in
                              (plan.aux_rows, plan.aux_cols, plan.aux_vals))
            self._sharded_fns = {}
        else:
            self._shards = [ops.device_arrays(sm) for sm in plan.shards]
            self._auxs = [
                (jnp.asarray(sm.aux_rows), jnp.asarray(sm.aux_cols),
                 jnp.asarray(sm.aux_vals)) if sm.n_aux else None
                for sm in plan.shards]
        held = ([*self._dev, *self._aux] if mesh is not None else
                [a for dev in self._shards for a in dev]
                + [a for aux in self._auxs if aux is not None for a in aux])
        if self._row_perm is not None:
            held = held + [self._row_perm]
        self._held = tuple(held)
        self._device_bytes = int(sum(int(a.nbytes) for a in held))

    # -- properties -------------------------------------------------------
    @property
    def nnz(self) -> int:
        return self.plan.nnz

    @property
    def value_dtype(self) -> str:
        """Precision of the streamed values ("float32" or "bfloat16");
        accumulation and outputs are fp32 either way."""
        return self.config.value_dtype

    @property
    def supports_fused_epilogue(self) -> bool:
        """Whether :meth:`matvec_fused` can run on this operator.

        The fused epilogue needs the *complete* accumulator resident at
        the kernel's last grid step, so it requires a single-shard plan
        (multi-shard needs a cross-shard combine first), no mesh, no
        aux spill side-stream (aux contributions land in a separate
        epilogue, after which acc would change under the fused hook), and
        no balanced-lane row permutation (the epilogue sees the virtual
        row order, not the caller's).
        """
        return (self.mesh is None and self.plan.num_shards == 1
                and self.plan.n_aux == 0 and self.plan.row_perm is None)

    @property
    def device_bytes(self) -> int:
        """Bytes of the device buffers this operator holds resident (the
        Serpens stream, its row-ordered copy and the aux spill triples) —
        what the registry's byte budget charges for a live binding."""
        return self._device_bytes

    @property
    def stream_devices(self) -> frozenset:
        """The devices that hold this operator's resident buffers — one per
        mesh-axis position for a mesh-bound plan."""
        return frozenset().union(*(a.sharding.device_set
                                   for a in self._held))

    @property
    def stream_bytes(self) -> int:
        return self.plan.stream_bytes

    @property
    def padding_ratio(self) -> float:
        return self.plan.padding_ratio

    @property
    def padded_slots(self) -> int:
        return int(self.plan.idx.size)

    def cost_report(self) -> dict:
        """Per-shard cost report counted from the plan (stream bytes,
        slots, padding) and the executor path of the bound backend.  See
        :func:`repro.obs.profile.plan_cost_report`."""
        from repro.obs import profile as _profile
        return {**_profile.plan_cost_report(self),
                "executor_path": ops.executor_path(self.backend)}

    def with_mesh(self, mesh, axis: str, partition: str | None = None
                  ) -> "SerpensOperator":
        """Rebind this operator's plan to a mesh axis.

        Reuses the encoded plan when its shard count matches the axis size;
        otherwise repartitions from the plan's COO (a host-side re-encode —
        prefer :meth:`MatrixRegistry.get` with a mesh, which caches the
        repartitioned plan).
        """
        if mesh is None:
            return self
        if axis is None:
            raise ValueError("mesh requires axis")
        n = mesh.shape[axis]
        plan = self.plan
        want = partition or (plan.spec.partition
                             if plan.spec.partition != "single" else "row")
        # Any 1-shard plan already is the 1-device stream — no re-encode.
        if plan.num_shards != n or (n > 1 and plan.spec.partition != want):
            r, c, v = plan.to_coo()
            plan = cpart.make_plan(
                r, c, v, self.shape, self.config,
                cpart.PlanSpec(want, n, plan.spec.lane_assign))
        return SerpensOperator(plan, mesh=mesh, axis=axis,
                               backend=self.backend)

    # -- compute ----------------------------------------------------------
    def _check_x(self, x, what: str):
        k = self.shape[1]
        if x.ndim < 1 or x.shape[0] != k:
            raise ValueError(
                f"{what} has shape {tuple(x.shape)}; matrix of shape "
                f"{self.shape} needs leading dimension K={k}")

    def _coerce(self, x, what: str):
        """Boundary dtype policy: floating inputs cast to the fp32 compute
        dtype exactly once, here — a float64 x must not silently promote
        the whole compute, and integer/bool inputs are a caller bug."""
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise TypeError(
                f"{what} must have a floating dtype, got {x.dtype} "
                f"(cast explicitly if an integer input is intentional)")
        return x.astype(jnp.float32)

    def matvec(self, x, backend: str | None = None):
        """Raw A @ x (no epilogue)."""
        x = self._coerce(x, "x")
        if x.ndim != 1:
            raise ValueError(
                f"matvec needs a 1-D x, got shape {tuple(x.shape)} "
                f"(use matmat for multi-vector)")
        self._check_x(x, "x")
        return self._apply(x, backend or self.backend)

    def __call__(self, x, alpha=1.0, beta=0.0, y=None, backend=None):
        """The paper's full SpMV: y_out = α·A·x + β·y (CompY epilogue)."""
        m, _ = self.shape
        acc = self.matvec(x, backend=backend)
        if y is None:
            y = jnp.zeros((m,), jnp.float32)
        else:
            y = self._coerce(y, "y")
        return float(alpha) * acc + float(beta) * y

    def matmat(self, x_mat, alpha=1.0, beta=0.0, y=None, backend=None):
        """Multi-vector SpMM (Sextans-style baseline / batched serving)."""
        x_mat = self._coerce(x_mat, "x_mat")
        if x_mat.ndim != 2:
            raise ValueError(
                f"matmat needs a (K, N) matrix, got shape "
                f"{tuple(x_mat.shape)}")
        self._check_x(x_mat, "x_mat")
        acc = self._apply(x_mat, backend or self.backend)
        if y is None:
            y = jnp.zeros_like(acc)
        else:
            y = self._coerce(y, "y")
        return float(alpha) * acc + float(beta) * y

    # -- fused epilogue (solver hot path) ---------------------------------
    def to_acc_layout(self, v):
        """Flat length-M vector → the kernel's (R, LANES) accumulator
        layout.  Lane-stationary rows put global row r at acc[r // LANES,
        r % LANES], so flat↔acc is a pure pad + reshape — solver vectors
        ride into the fused epilogue for free."""
        lanes = self.config.lanes
        rp = self.plan.out_rows_padded
        v = jnp.asarray(v, jnp.float32)
        return jnp.pad(v, (0, rp - v.shape[0])).reshape(-1, lanes)

    def from_acc_layout(self, a):
        """(R, LANES) accumulator layout → flat length-M vector."""
        return a.reshape(-1)[: self.shape[0]]

    def matvec_fused(self, x, epilogue, extras=(), backend=None):
        """One-pass ``A @ x`` + fused epilogue (see
        :func:`repro.kernels.ops.run_stream_fused`).

        ``epilogue(acc2d, *extras)`` receives the (R, LANES) fp32
        accumulator over *padded* rows (rows ≥ M are zero) and must
        return a tuple of arrays.  Only available when
        :attr:`supports_fused_epilogue`; callers (the solvers) fall back
        to the unfused two-pass path otherwise.

        Returns ``(acc_flat, outs)`` — ``acc_flat`` over padded rows
        (slice ``[:M]`` or use :meth:`from_acc_layout` on 2-D results).
        """
        if not self.supports_fused_epilogue:
            raise ValueError(
                "fused epilogue needs a single-shard, mesh-free plan with "
                "no aux spill and modulo lane assignment (got "
                f"shards={self.plan.num_shards}, mesh={self.mesh is not None}, "
                f"n_aux={self.plan.n_aux}, "
                f"lane_assign={self.plan.spec.lane_assign!r})")
        x = self._coerce(x, "x")
        if x.ndim != 1:
            raise ValueError("matvec_fused needs a 1-D x")
        self._check_x(x, "x")
        plan, cfg = self.plan, self.config
        kp = plan.num_segments_local * cfg.segment_width
        xp = jnp.pad(x, (0, kp - x.shape[0]))
        return ops.run_stream_fused(
            self._shards[0], xp, epilogue=epilogue, extras=extras,
            num_rows_padded=plan.out_rows_padded,
            segment_width=cfg.segment_width,
            tiles_per_chunk=cfg.tiles_per_chunk,
            backend=backend or self.backend)

    def _finish(self, acc):
        """Virtual accumulator → caller row order (leading axis).

        Modulo plans just drop the padding tail; balanced plans gather
        through the LPT permutation — one device gather in place of the
        slice, the entire runtime cost of ``lane_assign="balanced"``.
        """
        if self._row_perm is not None:
            return acc[self._row_perm]
        return acc[: self.shape[0]]

    def _shard_acc(self, dev, aux, xl, run):
        """One shard's accumulate + its aux-spill epilogue against local x."""
        acc = run(dev, xl)
        if aux is not None:
            ar, ac, av = aux
            contrib = av * xl[ac] if xl.ndim == 1 else av[:, None] * xl[ac]
            acc = acc.at[ar].add(contrib)
        return acc

    def _apply(self, x, backend):
        """Raw A @ x over the plan (x: 1-D or (K, N)) in caller row order."""
        plan, cfg = self.plan, self.config
        kp = plan.num_segments_local * cfg.segment_width
        x = x.astype(jnp.float32)
        run = functools.partial(
            ops.run_stream, num_rows_padded=plan.out_rows_padded,
            segment_width=cfg.segment_width,
            tiles_per_chunk=cfg.tiles_per_chunk, backend=backend)
        if self.mesh is not None:
            return self._apply_sharded(x, run, backend)
        pad = [(0, 0)] * x.ndim
        if plan.spec.partition == "col" and plan.num_shards > 1:
            pad[0] = (0, plan.num_shards * kp - x.shape[0])
            xp = jnp.pad(x, pad)
            acc = None
            for d, (dev, aux) in enumerate(zip(self._shards, self._auxs)):
                part = self._shard_acc(dev, aux, xp[d * kp:(d + 1) * kp],
                                       run)
                acc = part if acc is None else acc + part
            return self._finish(acc)
        pad[0] = (0, kp - x.shape[0])
        xp = jnp.pad(x, pad)
        outs = [self._shard_acc(dev, aux, xp, run)
                for dev, aux in zip(self._shards, self._auxs)]
        if plan.num_shards == 1:
            return self._finish(outs[0])
        return self._finish(
            jnp.concatenate([o[:plan.block_m] for o in outs]))

    def _apply_sharded(self, x, run, backend):
        """shard_map execution over the mesh axis (row concat / col psum)."""
        plan, axis = self.plan, self.axis
        n = plan.num_shards
        kp = plan.num_segments_local * self.config.segment_width
        col = plan.spec.partition == "col"
        pad = [(0, 0)] * x.ndim
        if col:
            pad[0] = (0, n * kp - x.shape[0])
            xp = jnp.pad(x, pad).reshape((n, kp) + x.shape[1:])
            x_spec = P(axis)
        else:
            pad[0] = (0, kp - x.shape[0])
            xp = jnp.pad(x, pad)
            x_spec = P()

        # One jitted shard_map per backend: a fresh closure on every call
        # would retrace and recompile the whole program.
        f = self._sharded_fns.get(backend)
        if f is None:
            def body(dev, ar, ac, av, xv):
                xl = xv[0] if col else xv
                acc = self._shard_acc(jax.tree.map(lambda a: a[0], dev),
                                      (ar[0], ac[0], av[0]), xl, run)
                if col:
                    return jax.lax.psum(acc, axis)
                return acc[None]

            f = jax.jit(compat.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(axis),) * 4 + (x_spec,),
                out_specs=P() if col else P(axis),
                check_rep=False))  # pallas_call has no replication rule
            self._sharded_fns[backend] = f
        acc = f(self._dev, *self._aux, xp)
        if col:
            return self._finish(acc)
        acc = acc[:, :plan.block_m]
        return self._finish(acc.reshape((-1,) + acc.shape[2:]))

    def to_dense(self) -> np.ndarray:
        """Densify (testing only)."""
        r, c, v = self.plan.to_coo()
        out = np.zeros(self.shape, np.float32)
        np.add.at(out, (r, c), v)
        return out


class SerpensSpMV(SerpensOperator):
    """The classic single-shard operator: one Serpens stream, one device."""

    def __init__(self, rows, cols, vals, shape,
                 config: sformat.SerpensConfig = sformat.SerpensConfig(),
                 backend: str = "auto"):
        plan = cpart.make_plan(rows, cols, vals, shape, config,
                               cpart.PlanSpec())
        super().__init__(plan, backend=backend)
        self.host = plan.shards[0]


def from_dense(a: np.ndarray, config=sformat.SerpensConfig(),
               backend="auto") -> SerpensSpMV:
    rows, cols = np.nonzero(a)
    return SerpensSpMV(rows, cols, a[rows, cols], a.shape, config, backend)


class ShardedSerpensSpMV(SerpensOperator):
    """Row- or column-partitioned SpMV over one mesh axis.

    The paper scales by adding HBM channels (Sec. 4.4, 16 → 24 channels,
    Table 5); on a TPU mesh the analogous scaling axis is *chips*.  This
    builds a channel-shard plan over the mesh axis and executes it through
    the same :class:`SerpensOperator` as the single-device path — the aux
    spill stream, both backends, and matmat all work sharded.

      * ``row``: each device owns a contiguous row block and its own stream;
        x is replicated; outputs concatenate (no inter-device reduction).
      * ``col``: segments sharded; each device produces a partial full-length
        y; a ``psum`` combines (for very large K where x must shard).
    """

    def __init__(self, rows, cols, vals, shape, mesh, axis: str,
                 partition: str = "row",
                 config: sformat.SerpensConfig = sformat.SerpensConfig(),
                 backend: str = "auto"):
        if partition not in ("row", "col"):
            raise ValueError("partition must be 'row' or 'col'")
        plan = cpart.make_plan(
            rows, cols, vals, shape, config,
            cpart.PlanSpec(partition, mesh.shape[axis]))
        super().__init__(plan, mesh=mesh, axis=axis, backend=backend)
        self.partition = partition
