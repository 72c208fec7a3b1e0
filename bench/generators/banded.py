"""Banded matrix: the stand-in for SuiteSparse FEM matrices.

Every row holds the columns within ``half_width`` of its diagonal, as in
``repro.data.matrices.banded``: regular rows, a local gather and no hot
rows.  The structure is fixed by the sizes; ``--seed`` draws the values,
standard normal float32, so every seed does the same work.
"""
from __future__ import annotations

import numpy as np


def generate(spec: dict, seed: int):
    """``(rows, cols, vals, shape)`` of the stand-in for ``seed``."""
    n, hw = int(spec["rows"]), int(spec["half_width"])
    if spec["cols"] != n:
        raise ValueError("banded makes square matrices")
    offs = np.arange(-hw, hw + 1, dtype=np.int64)
    rows = np.repeat(np.arange(n, dtype=np.int64), offs.size)
    cols = rows + np.tile(offs, n)
    keep = (cols >= 0) & (cols < n)
    rows, cols = rows[keep], cols[keep]
    vals = np.random.default_rng(seed).standard_normal(rows.size,
                                                       dtype=np.float32)
    return rows, cols, vals, (n, n)
