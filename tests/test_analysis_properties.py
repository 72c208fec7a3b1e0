"""Property tests: the stream verifier as an oracle over random encodes.

Mirrors ``test_format_properties.py``: hypothesis generates (COO, config,
spec) triples; every encoder output must verify clean against its source,
and any single live-slot corruption must be caught.  Skipped wholesale when
hypothesis isn't installed (it is in CI).
"""
import pytest

pytest.importorskip("hypothesis")

import dataclasses  # noqa: E402

import numpy as np  # noqa: E402
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.analysis import verify_matrix, verify_plan  # noqa: E402
from repro.core import format as F  # noqa: E402
from repro.core import partition as PT  # noqa: E402

CONFIGS = st.builds(
    F.SerpensConfig,
    segment_width=st.sampled_from([16, 64, 256]),
    lanes=st.sampled_from([2, 4, 8]),
    sublanes=st.sampled_from([2, 4]),
    raw_window=st.integers(1, 4),
    tiles_per_chunk=st.sampled_from([1, 2]),
    value_dtype=st.sampled_from(["float32", "bfloat16"]),
    spill_hot_rows=st.booleans(),
    lane_balance=st.sampled_from([0.0, 1.2]))

LANE_ASSIGNS = st.sampled_from(["modulo", "balanced"])
# A "single" plan has exactly one shard (PlanSpec rejects any other count).
SPECS = st.one_of(
    st.builds(PT.PlanSpec, partition=st.just("single"),
              num_shards=st.just(1), lane_assign=LANE_ASSIGNS),
    st.builds(PT.PlanSpec, partition=st.sampled_from(["row", "col"]),
              num_shards=st.integers(1, 3), lane_assign=LANE_ASSIGNS))

COOS = st.builds(
    lambda m, k, nnz, seed: (m, k, *_coo(m, k, nnz, seed)),
    m=st.integers(1, 60), k=st.integers(1, 80),
    nnz=st.integers(0, 250), seed=st.integers(0, 2**31))


def _coo(m, k, nnz, seed):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m, nnz)
    cols = rng.integers(0, k, nnz)
    vals = rng.normal(size=nnz).astype(np.float32)
    return rows, cols, vals


@settings(max_examples=60, deadline=None)
@given(coo=COOS, cfg=CONFIGS, spec=SPECS)
def test_every_plan_verifies_clean(coo, cfg, spec):
    m, k, rows, cols, vals = coo
    plan = PT.make_plan(rows, cols, vals, (m, k), cfg, spec)
    d = verify_plan(plan, rows, cols, vals, mode="full")
    assert d.ok, d.format()


@settings(max_examples=40, deadline=None)
@given(coo=COOS, cfg=CONFIGS, slot=st.integers(0, 2**31))
def test_single_slot_corruption_is_caught(coo, cfg, slot):
    """Flipping any one live slot's column bit breaks the source proof."""
    m, k, rows, cols, vals = coo
    sm = F.encode(rows, cols, vals, (m, k), cfg)
    live = np.argwhere(np.asarray(sm.idx) != F.SENTINEL)
    if live.size == 0:
        return
    t, s, lane = (int(x) for x in live[slot % len(live)])
    idx = np.array(sm.idx)
    # XOR the column low bit: stays inside the (even-width) segment, so
    # only the round-trip-vs-source rule can see it — the sharpest oracle.
    idx[t, s, lane] ^= np.int32(1)
    bad = dataclasses.replace(sm, idx=idx)
    d = verify_matrix(bad, source=(rows, cols, vals))
    assert not d.ok
