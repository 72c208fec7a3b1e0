"""The plain reference: float64 products over the COO triples on the host.

Written against the same seeded triples the program is given, with
scipy's CSR product in float64 and nothing of the program imported.
Each compared number is one float; ``bench/configs/<config>.json``
holds its limit.

``A`` is held as ``threads`` blocks of whole rows, multiplied in as many
threads (scipy and numpy release the interpreter lock over large
arrays): each row's sum is the same loop over the same CSR row as in one
product of the whole matrix, so the answers are the same to the bit,
in a fraction of one thread's time.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp


class Reference:
    """``A`` in float64 CSR row blocks, plus ``|A|`` for the rounding
    scale; a context manager that owns the threads it multiplies in."""

    def __init__(self, rows, cols, vals, shape, threads: int = 1):
        vals = np.asarray(vals, np.float64)
        a = sp.csr_matrix((vals, (rows, cols)), shape=shape)
        cuts = np.linspace(0, shape[0], threads + 1).astype(np.int64)
        self.blocks = [a[lo:hi] for lo, hi in zip(cuts[:-1], cuts[1:])]
        del a
        self.abs_blocks = [abs(b) for b in self.blocks]
        self.shape = shape
        self.executor = ThreadPoolExecutor(threads)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.executor.shutdown()

    def _times(self, blocks, x: np.ndarray) -> np.ndarray:
        """``A x`` (or ``|A| x``) from the row blocks, in the threads."""
        return np.concatenate(list(self.executor.map(lambda b: b @ x, blocks)))

    def products(self, xs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """``(A x, |A| |x|)`` in float64 for each row ``x`` of ``xs``."""
        x = np.asarray(xs, np.float64).T
        return (self._times(self.blocks, x).T,
                self._times(self.abs_blocks, np.abs(x)).T)

    def rel_gaps(self, pairs) -> list[float]:
        """:func:`rel_gap` of each ``(y, ref, mag)``, in the threads."""
        return list(self.executor.map(lambda p: rel_gap(*p), pairs))

    def pagerank(self, damping: float, tol: float = 1e-13,
                 max_iters: int = 1000) -> np.ndarray:
        """The float64 fixed point of ``r <- d A r + (1 - sum(d A r)) / n``
        from the uniform start (dangling mass returns uniformly)."""
        n = self.shape[0]
        r = np.full(n, 1.0 / n)
        for _ in range(max_iters):
            link = damping * self._times(self.blocks, r)
            r_new = link + (1.0 - link.sum()) / n
            delta = np.abs(r_new - r).sum()
            r = r_new
            if delta <= tol:
                return r
        raise RuntimeError(f"reference PageRank did not reach {tol:g} "
                           f"in {max_iters} iterations")


def l1_gap(r: np.ndarray, r_ref: np.ndarray) -> float:
    """``||r - r_ref||_1``; a vector of the wrong shape or with a
    non-finite entry is infinitely far."""
    r = np.asarray(r, np.float64)
    if r.shape != r_ref.shape or not np.isfinite(r).all():
        return float("inf")
    return float(np.abs(r - r_ref).sum())


def rel_gap(y: np.ndarray, ref: np.ndarray, mag: np.ndarray) -> float:
    """Widest ``|y - A x| / (|A| |x|)`` over the rows: the elementwise
    error in units of the product's own rounding scale.  A row with
    ``|A| |x| = 0`` must be exactly 0; a vector of the wrong shape or
    with a non-finite entry is infinitely far."""
    y = np.asarray(y, np.float64)
    if y.shape != ref.shape or not np.isfinite(y).all():
        return float("inf")
    err = np.abs(y - ref)
    scaled = err / np.where(mag > 0, mag, 1.0)
    scaled[(mag == 0) & (err > 0)] = np.inf
    return float(scaled.max(initial=0.0))
