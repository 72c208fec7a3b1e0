"""Power-law directed graph: the stand-in for SNAP social graphs.

Out- and in-endpoints are drawn independently with probability
``p_i ∝ (i + offset)^-exponent``, ``offset = max(10, n / 100)``, as in
``repro.data.matrices.power_law_graph``; duplicate edges merge, and
vertex ids are relabelled by a permutation so hot rows spread over the
stream.  Two things differ from that generator, neither in distribution:

* Speed.  Per-vertex counts come from one multinomial draw and the edges
  from ``np.repeat`` plus one shuffle, which is the same law as
  ``rng.choice(p=...)`` draws one by one (an iid sequence is its
  multinomial counts in uniformly random order).  Duplicates merge with
  one sort of int64 keys in place of ``np.unique`` plus ``np.add.at``.
* Seeding.  The edge set comes from the configuration's fixed
  ``structure_seed``; ``--seed`` picks the relabelling.  Every seed then
  has the same degree sequence and the same PageRank iteration count,
  in another vertex order, so the seed changes the layout and not the
  amount of work.

Values: ``"pagerank"`` gives the column-stochastic matrix PageRank users
load, ``A[i, j] = 1 / outdeg(j)`` for an edge ``j -> i``.
"""
from __future__ import annotations

import numpy as np


def degree_weights(n: int, exponent: float) -> np.ndarray:
    offset = max(10.0, n / 100.0)
    p = (np.arange(n, dtype=np.float64) + offset) ** (-exponent)
    return p / p.sum()


def generate(spec: dict, seed: int):
    """``(rows, cols, vals, shape)`` of the stand-in for ``seed``."""
    n, edges = int(spec["rows"]), int(spec["edges"])
    if spec["cols"] != n:
        raise ValueError("power_law makes square matrices")
    if spec["values"] != "pagerank":
        raise ValueError(f"unknown values {spec['values']!r}")
    base = np.random.default_rng(int(spec["structure_seed"]))
    p = degree_weights(n, float(spec["exponent"]))
    ids = np.arange(n, dtype=np.int64)
    rows = np.repeat(ids, base.multinomial(edges, p))
    cols = np.repeat(ids, base.multinomial(edges, p))
    base.shuffle(cols)                 # random pairing of the endpoints
    perm = np.random.default_rng(seed).permutation(n)
    keys = np.unique(perm[rows] * n + perm[cols])    # sorted, merged
    rows, cols = np.divmod(keys, n)
    outdeg = np.bincount(cols, minlength=n)
    vals = (1.0 / outdeg[cols]).astype(np.float32)
    return rows, cols, vals, (n, n)
