"""Smoke run of the SpMV serving path on the TPU.

Drives the main path once through the entry points a user calls —
``MatrixRegistry.put`` -> channel-shard plan -> ``SerpensOperator`` -> the
stream executor, served by ``SpMVService`` — on the G7 stand-in
(soc_pokec: 1.63M x 1.63M, ~30.6M nnz, power-law) at published size,
generated from a seed.  Every result is checked against a float64 host
reference; any failed phase fails the run.

    python chip_smoke.py             # one chip: fp32 and bf16 SpMV/SpMM,
                                     # pipelined serving, fused PageRank
    python chip_smoke.py --chips 4   # only the mesh path: row and col
                                     # plans over 4 chips

Timings printed on the way are information only.  The last line of
standard output is one JSON object, ``{"ok": true, "device": {...}}``.
Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import logging
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
GID = "G7"
SEED = 0
N_COALESCED = 8                # requests coalesced by flush() into one SpMM
N_PIPELINED = 3                # requests served with the stage threads on
F32_RTOL = 1e-4                # of |A|.|x|, elementwise
BF16_RTOL = 2.0 ** -8          # README's bf16 stream bound, of |A|.|x|
PAGERANK_DAMPING = 0.85
PAGERANK_L1_TOL = 1e-5         # host float64 fixed-point residual
RESULT_TIMEOUT_S = 600.0
BYTE_BUDGET = 24 << 30         # keep every entry's prepared arrays resident


class SmokeFailure(Exception):
    """A result that is wrong, missing or carries an error."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _LoggedFailures(logging.Handler):
    """Records warnings and errors that the serving tier logs and contains
    (a dispatcher iteration that failed and carried on) so they fail the
    run instead of ending in exit 0."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages: list[str] = []

    def emit(self, record):
        self.messages.append(f"{record.name}: {record.getMessage()}")


def _timed(fn, warm_iters: int = 5) -> tuple[float, float]:
    """(first call incl. compile, warm median) seconds, device-blocked."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    times = []
    for _ in range(warm_iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return first, float(np.median(times))


class HostReference:
    """float64 ``A @ x`` and ``|A| @ |x|`` on the host."""

    def __init__(self, rows, cols, vals, m):
        self.rows, self.cols, self.m = rows, cols, m
        self.vals = np.asarray(vals, np.float64)
        self.abs_vals = np.abs(self.vals)

    def spmv(self, x):
        x = np.asarray(x, np.float64)
        ref = np.bincount(self.rows, self.vals * x[self.cols],
                          minlength=self.m)
        mag = np.bincount(self.rows, self.abs_vals * np.abs(x[self.cols]),
                          minlength=self.m)
        return ref, mag

    def check(self, y, x, rtol: float, what: str) -> None:
        """Elementwise ``|y - A x| <= rtol * |A| |x|``."""
        y = np.asarray(y)
        check(y.shape == (self.m,), f"{what}: shape {y.shape}, expected "
                                   f"({self.m},)")
        check(bool(np.isfinite(y).all()), f"{what}: non-finite values")
        ref, mag = self.spmv(x)
        err = np.abs(y.astype(np.float64) - ref)
        bound = rtol * mag
        if not (err <= bound + 1e-30).all():
            worst = float(np.max(err / np.maximum(bound, 1e-300)))
            raise SmokeFailure(f"{what}: |y - Ax| exceeds {rtol:g}*|A||x| "
                               f"(worst error/bound {worst:.3g})")


def _vectors(n_vec: int, k: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n_vec, k), dtype=np.float32)


def _serve_checked(svc, mid, xs, ref, rtol, what) -> None:
    """Submit ``xs``, coalesce them with one ``flush()``, check each."""
    tickets = [svc.submit(mid, x) for x in xs]
    results = svc.flush()
    for i, t in enumerate(tickets):
        check(t in results, f"{what}: ticket {t} missing from flush")
        res = results[t]
        check(res.error is None, f"{what}: request {i} failed: {res.error!r}")
        check(res.batch_size == len(xs),
              f"{what}: request {i} served in a batch of {res.batch_size}, "
              f"expected {len(xs)}")
        ref.check(res.y, x=xs[i], rtol=rtol, what=f"{what} request {i}")


def serve_one_chip(scale: float = 1.0, seed: int = SEED) -> None:
    """fp32 and bf16 SpMV/SpMM through the service (coalesced and
    pipelined), then one fused PageRank solve."""
    from repro.core.registry import MatrixRegistry
    from repro.data import matrices
    from repro.serve.spmv_service import SpMVService

    rows, cols, vals, shape, meta = matrices.paper_matrix(GID, scale=scale,
                                                          seed=seed)
    m, k = shape
    print(f"matrix {GID} ({meta['name']} stand-in): {m} x {k}, "
          f"nnz {rows.size}", flush=True)
    ref = HostReference(rows, cols, vals, m)
    xs = _vectors(N_COALESCED, k, seed + 1)
    reg = MatrixRegistry(byte_budget=BYTE_BUDGET, verify="fast")
    try:
        for dtype, rtol in (("float32", F32_RTOL), ("bfloat16", BF16_RTOL)):
            t0 = time.perf_counter()
            mid = reg.put(rows, cols, vals, shape, value_dtype=dtype)
            put_s = time.perf_counter() - t0
            op = reg.get(mid)
            print(f"[{dtype}] backend={op.backend} nnz={op.nnz} "
                  f"stream_bytes={op.stream_bytes} "
                  f"padding_ratio={op.padding_ratio:.4f} put_s={put_s:.3f}",
                  flush=True)
            mv_first, mv_warm = _timed(lambda: op.matvec(xs[0]))
            xm = np.ascontiguousarray(xs.T)
            mm_first, mm_warm = _timed(lambda: op.matmat(xm))
            print(f"[{dtype}] spmv first_call_s={mv_first:.3f} "
                  f"warm_s={mv_warm:.6f}; spmm n={N_COALESCED} "
                  f"first_call_s={mm_first:.3f} warm_s={mm_warm:.6f}",
                  flush=True)

            svc = SpMVService(reg, max_bucket=N_COALESCED)
            _serve_checked(svc, mid, xs, ref, rtol, f"[{dtype}] coalesced")
            with svc:
                tickets = [svc.submit(mid, xs[i]) for i in range(N_PIPELINED)]
                for i, t in enumerate(tickets):
                    res = svc.result(t, timeout=RESULT_TIMEOUT_S)
                    ref.check(res.y, x=xs[i], rtol=rtol,
                              what=f"[{dtype}] pipelined request {i}")
            print(f"[{dtype}] served {N_COALESCED} coalesced + "
                  f"{N_PIPELINED} pipelined requests: ok", flush=True)

        pr_vals = matrices.column_normalize(rows, cols, vals, m)
        mid = reg.put(rows, cols, pr_vals, shape)
        svc = SpMVService(reg)
        t0 = time.perf_counter()
        res = svc.solve(mid, "pagerank", timeout=RESULT_TIMEOUT_S,
                        damping=PAGERANK_DAMPING, tol=1e-6, max_iters=100)
        solve_s = time.perf_counter() - t0
        check(res.error is None, f"pagerank failed: {res.error!r}")
        check(res.solve.fused, "pagerank did not take the fused epilogue")
        r = np.asarray(res.y, np.float64)
        check(r.shape == (m,) and bool(np.isfinite(r).all()),
              "pagerank: bad or non-finite result")
        pr_ref = HostReference(rows, cols, pr_vals, m)
        link = PAGERANK_DAMPING * pr_ref.spmv(r)[0]
        residual = float(np.abs(link + (1.0 - link.sum()) / m - r).sum())
        print(f"[pagerank] fused={res.solve.fused} "
              f"iterations={res.solve.iterations} "
              f"device_l1_delta={res.solve.residual:.3e} "
              f"host_l1_residual={residual:.3e} sum={r.sum():.6f} "
              f"solve_s={solve_s:.3f}", flush=True)
        check(residual <= PAGERANK_L1_TOL,
              f"pagerank: host L1 residual {residual:.3e} > "
              f"{PAGERANK_L1_TOL:g}")
        check(abs(r.sum() - 1.0) <= 1e-3 and r.min() >= -1e-9,
              "pagerank: result is not a probability vector")
    finally:
        reg.close()


def serve_mesh(n_chips: int, scale: float = 1.0, seed: int = SEED) -> None:
    """Row and col plans of the same matrix over ``n_chips`` devices,
    through ``registry.get(mesh=)`` and ``SpMVService(mesh=)``."""
    import jax
    from jax.sharding import Mesh
    from repro.core.registry import MatrixRegistry
    from repro.data import matrices
    from repro.serve.spmv_service import SpMVService

    devices = jax.devices()
    check(len(devices) >= n_chips,
          f"--chips {n_chips} needs {n_chips} devices, found {len(devices)}")
    mesh = Mesh(np.array(devices[:n_chips]), ("chips",))
    rows, cols, vals, shape, meta = matrices.paper_matrix(GID, scale=scale,
                                                          seed=seed)
    m, k = shape
    print(f"matrix {GID} ({meta['name']} stand-in): {m} x {k}, "
          f"nnz {rows.size}; mesh of {n_chips}", flush=True)
    ref = HostReference(rows, cols, vals, m)
    xs = _vectors(N_COALESCED, k, seed + 1)
    reg = MatrixRegistry(byte_budget=BYTE_BUDGET, verify="fast")
    try:
        # Encoded as a row plan; the col plan is a repartition by get().
        mid = reg.put(rows, cols, vals, shape, partition="row",
                      num_shards=n_chips)
        for partition in ("row", "col"):
            t0 = time.perf_counter()
            op = reg.get(mid, mesh=mesh, axis="chips", partition=partition)
            bind_s = time.perf_counter() - t0
            check(op.plan.spec.partition == partition
                  and op.plan.num_shards == n_chips,
                  f"[{partition}] plan is {op.plan.spec}")
            held = op.stream_devices
            check(len(held) == n_chips,
                  f"[{partition}] streams on {len(held)} devices, "
                  f"expected {n_chips}")
            mv_first, mv_warm = _timed(lambda: op.matvec(xs[0]))
            print(f"[{partition} x{n_chips}] backend={op.backend} "
                  f"stream_bytes={op.stream_bytes} devices={len(held)} "
                  f"repartition_bind_s={bind_s:.3f} "
                  f"spmv first_call_s={mv_first:.3f} warm_s={mv_warm:.6f}",
                  flush=True)
            svc = SpMVService(reg, max_bucket=N_COALESCED, mesh=mesh,
                              axis="chips", partition=partition)
            for i in range(2):
                _serve_checked(svc, mid, xs[i:i + 1], ref, F32_RTOL,
                               f"[{partition} x{n_chips}] spmv {i}")
            _serve_checked(svc, mid, xs, ref, F32_RTOL,
                           f"[{partition} x{n_chips}] spmm")
            print(f"[{partition} x{n_chips}] served 2 SpMV + one SpMM batch "
                  f"of {N_COALESCED}: ok", flush=True)
    finally:
        reg.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path (row and col plans)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke.py: no src/repro next to {ROOT}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform "
              f"{dev.platform!r}); this smoke run has no CPU path",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}", flush=True)

    logged = _LoggedFailures()
    for name in ("repro.serve", "repro.registry"):
        logging.getLogger(name).addHandler(logged)
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            serve_one_chip()
        else:
            serve_mesh(args.chips)
        check(not logged.messages,
              f"the serving tier logged failures: {logged.messages}")
    except Exception:  # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        return 1
    print(f"total_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
