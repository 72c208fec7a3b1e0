"""Tests of the chip benchmark's yardstick, on the CPU at small sizes.

Covers the work counts, the trace reduction (on a trace recorded on a
v5e, ``data/trace.xplane.pb``), the arrival generators, the stand-in
generators, the lookup of cells, mixes and metrics by name, the contract
``BENCHMARK.json`` keeps, and the correctness check: a sound run passes,
and the bfloat16 control and faults planted under the timed path fail.
"""
from __future__ import annotations

import json
import math
import re
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import loadgen  # noqa: E402
import run as bench_run  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

TRACE = BENCH / "tests" / "data" / "trace.xplane.pb"
BIG_SEED = 2**31 + 977                 # seeds may pass 32 bits
TINY = {"pokec": {"rows": 3000, "cols": 3000, "edges": 30000},
        "ml_laplace": {"rows": 3000, "cols": 3000}}


@pytest.fixture(scope="module")
def bench():
    return bench_run.load_benchmark()


# -- work counts ------------------------------------------------------------

def test_spmv_work_hand_counts():
    # 4 x 3 matrix with 5 non-zeros: 5 * (4 + 4) + 4 * (4 + 3) bytes.
    assert work.spmv_work(5, (4, 3), "float32") == (68, 10)
    assert work.spmv_work(5, (4, 3), "float32", 3) == (40 + 84, 30)
    assert work.spmv_work(5, (4, 3), "bfloat16") == (30 + 28, 10)
    with pytest.raises(ValueError):
        work.spmv_work(5, (4, 3), "float32", 0)


def test_window_work_is_the_sum_over_calls():
    calls = [1, 2, 16, 5]
    by_call = [work.spmv_work(1000, (300, 200), "float32", n) for n in calls]
    assert work.window_work(1000, (300, 200), "float32", len(calls),
                            sum(calls)) == tuple(map(sum, zip(*by_call)))
    assert work.window_work(1000, (300, 200), "float32", 0, 0) == (0, 0)


def _run_record(config, mix, nnz, shape, calls, vectors, slots):
    return bench_run.Run(cell="c", config=config, mix=mix, seconds=1.0,
                         nnz=nnz, shape=shape, value_dtype="float32",
                         op_nnz=nnz, op_padded_slots=slots, calls=calls,
                         vectors=vectors)


@pytest.mark.parametrize("plan", [
    {"lane_assign": "modulo"},
    {"lane_assign": "balanced"},
    {"partition": "row", "num_shards": 2},
])
def test_work_ignores_padding_spill_and_lane_assignment(plan):
    """Plans of one matrix pad differently; the counted work does not."""
    from repro.core.registry import MatrixRegistry
    from repro.data import matrices
    rows, cols, vals = matrices.power_law_graph(2000, 20000, seed=3)
    reg = MatrixRegistry()
    try:
        base = reg.get(reg.put(rows, cols, vals, (2000, 2000)))
        op = reg.get(reg.put(rows, cols, vals, (2000, 2000), **plan))
        slots = {base.padded_slots, op.padded_slots}
    finally:
        reg.close()
    mix = {"kind": "closed"}
    counted = {_run_record({}, mix, rows.size, (2000, 2000), 7, 40, s).work()
               for s in slots}
    assert counted == {work.window_work(rows.size, (2000, 2000), "float32",
                                        7, 40)}
    assert min(slots) >= rows.size


# -- trace reduction --------------------------------------------------------

@pytest.fixture(scope="module")
def profile():
    return trace_reduce.load(TRACE)


def _naive_busy_ns(profile, lo, hi) -> int:
    """Busy time by marking microseconds: an independent union."""
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    marks = np.zeros((hi - lo) // 1000 + 1, bool)
    for line in plane.lines:
        if line.name in trace_reduce.BUSY_LINES:
            for ev in line.events:
                a = max(int(ev.start_ns), lo)
                b = min(int(ev.start_ns + ev.duration_ns), hi)
                if b > a:
                    marks[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    return int(marks.sum()) * 1000


def test_trace_busy_union_and_idle_share(profile):
    red = trace_reduce.reduce_profile(profile)
    lo, hi = trace_reduce.find_span(trace_reduce.host_events(profile),
                                    trace_reduce.WINDOW)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    # The microsecond marks overcount each interval by under 2 us.
    naive = _naive_busy_ns(profile, lo, hi) / 1e9
    assert red["busy_s"] <= naive <= red["busy_s"] + 2e-6 * 10_000
    idle = sum(s for s in red["idle_by_activity"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)


def test_trace_top_ops_and_labelled_gaps(profile):
    red = trace_reduce.reduce_profile(profile)
    ops = red["device_ops"]
    assert 0 < len(ops) <= trace_reduce.TOP
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert all("/" in name and "(" not in name for name, _ in ops)
    assert sum(s for _, s in ops) <= red["busy_s"] * (1 + 1e-9)
    assert any(name.startswith("jit_spmm_stream_xla/")
               or name.startswith("jit_spmv_stream_xla/") for name, _ in ops)
    gaps = red["idle_gaps"]
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert {name for name, _ in gaps} <= set(red["idle_by_activity"])
    assert all(name.startswith(("bench.", "repro.", "xla.", "idle host"))
               for name in red["idle_by_activity"])


def test_label_gap_prefers_the_most_specific_cover():
    acts = [("bench.result_wait", 0, 1000), ("repro.pack", 100, 300),
            ("xla.compile", 700, 760)]
    assert trace_reduce.label_gap((120, 280), acts) == "repro.pack"
    assert trace_reduce.label_gap((400, 600), acts) == "bench.result_wait"
    assert trace_reduce.label_gap((2000, 2100), acts) == "idle host"
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [
        (0, 4), (5, 10)]


def test_clock_offset_maps_the_sync_annotation():
    events = [("bench.sync", 5_000, 5_010), ("other", 0, 1)]
    assert trace_reduce.clock_offset_ns(events, 123) == 5_000 - 123
    with pytest.raises(ValueError):
        trace_reduce.find_span(events, "bench.window")


# -- load generation --------------------------------------------------------

def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert loadgen.percentile(xs, 90) == 90
    assert loadgen.percentile(xs, 99) == 99
    assert loadgen.percentile([3.0], 90) == 3.0
    assert loadgen.percentile([5, 1, 4, 2, 3], 50) == 3
    assert loadgen.percentile([], 90) == 0.0


def test_poisson_arrivals_same_gaps_in_another_order():
    a = loadgen.poisson_arrivals(2.0, 50.0, np.random.default_rng(BIG_SEED))
    b = loadgen.poisson_arrivals(2.0, 50.0, np.random.default_rng(7))
    assert a.size == b.size == 100
    assert a[-1] == pytest.approx(50.0) and b[-1] == pytest.approx(50.0)
    assert np.all(np.diff(a) > 0)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
    gaps = np.diff(a, prepend=0)
    # Exponential gaps: the coefficient of variation is near 1.
    assert statistics.pstdev(gaps) / gaps.mean() == pytest.approx(1, abs=0.15)


def test_onoff_arrivals_keep_count_and_silence():
    rng = np.random.default_rng(BIG_SEED)
    t = loadgen.onoff_arrivals(2.0, 40.0, rng, on_s=2.0, off_s=2.0)
    assert t.size == 80
    assert np.all((t % 4.0) <= 2.0 + 1e-9)          # nothing in OFF phases
    assert t.max() <= 40.0
    with pytest.raises(ValueError):
        loadgen.arrivals({"arrivals": "zipf", "rate": 1.0}, 1.0, rng)


# -- stand-in generators ----------------------------------------------------

def test_power_law_stand_in_matches_the_repo_generator():
    from repro.data import matrices
    spec = dict(bench_run.load_json("configs", "pokec")["matrix"],
                rows=20000, cols=20000, edges=200000)
    gen = bench_run.load_reader("generators", "power_law")
    rows, cols, vals, shape = gen.generate(spec, BIG_SEED)
    r0, c0, _ = matrices.power_law_graph(20000, 200000, seed=1)
    assert shape == (20000, 20000)
    assert rows.size == pytest.approx(r0.size, rel=0.01)     # same dedupe
    keys = rows * 20000 + cols
    assert np.unique(keys).size == keys.size
    for ours, theirs in ((rows, r0), (cols, c0)):
        deg, deg0 = np.bincount(ours), np.bincount(theirs)
        assert deg.max() == pytest.approx(deg0.max(), rel=0.25)
        assert (np.sort(deg)[-200:].sum()
                == pytest.approx(np.sort(deg0)[-200:].sum(), rel=0.05))
    colsum = np.bincount(cols, weights=vals, minlength=20000)
    np.testing.assert_allclose(colsum[np.bincount(cols,
                                                  minlength=20000) > 0], 1.0,
                               rtol=1e-5)
    # Another seed relabels the same edge set: same degree sequences.
    rows2, cols2, _, _ = gen.generate(spec, 11)
    assert np.array_equal(np.sort(np.bincount(rows2, minlength=20000)),
                          np.sort(np.bincount(rows, minlength=20000)))
    assert not np.array_equal(rows2, rows)


def test_banded_stand_in_sizes():
    spec = dict(bench_run.load_json("configs", "ml_laplace")["matrix"],
                rows=5000, cols=5000)
    gen = bench_run.load_reader("generators", "banded")
    rows, cols, vals, shape = gen.generate(spec, BIG_SEED)
    hw = spec["half_width"]
    assert rows.size == 5000 * (2 * hw + 1) - hw * (hw + 1)
    assert np.bincount(rows)[hw:-hw].min() == 2 * hw + 1
    assert np.abs(rows - cols).max() == hw
    assert vals.dtype == np.float32
    assert not np.array_equal(vals, gen.generate(spec, 5)[2])


# -- lookup by name and the contract ----------------------------------------

def test_cells_mixes_and_metrics_are_found_by_name(bench):
    for cell in bench["workloads"]:
        assert bench_run.load_json("configs", cell["config"])["name"] == \
            cell["config"]
        assert bench_run.load_json("traffic", cell["traffic"])["kind"] in (
            "solve", "closed", "open")
        for trace in (False, True):
            metrics = bench_run.cell_metrics(bench, cell["name"], trace)
            assert metrics
            kind = "layer_metrics" if trace else "end_to_end"
            for m in metrics:
                assert callable(bench_run.load_reader(kind, m["name"]).read)
    for config in bench["configs"]:
        cfg = json.loads((ROOT / config["file"]).read_text())
        bench_run.load_reader("generators", cfg["matrix"]["generator"])


def test_peaks_table_and_unknown_device(bench):
    peaks = bench_run.device_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["source"]
    with pytest.raises(KeyError):
        bench_run.device_peaks("TPU v9 imaginary")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    # A full check with 24 cells fits: 2 + 14 runs per cell.
    assert ((2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200
            <= 43200)
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in bench[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    cells = {c["name"] for c in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for cell in cells:
        reported = [m for m in bench["end_to_end"]
                    if cell in m.get("workloads", [cell])]
        assert len(reported) >= 2
        assert bench_run.cell_metrics(bench, cell, True)
    assert all(c["chips"] == 1 for c in bench["workloads"])


# -- the correctness check: sound, control, faults ---------------------------

def _tiny_run(bench, cell_name, seconds=1.0, rate=20.0, **config_changes):
    cell = next(c for c in bench["workloads"] if c["name"] == cell_name)
    config = bench_run.load_json("configs", cell["config"])
    config["matrix"].update(TINY[cell["config"]])
    config.update(config_changes)
    mix = bench_run.load_json("traffic", cell["traffic"])
    if mix["kind"] == "open":
        mix["rate"] = rate
    return bench_run.run_cell(bench, cell_name, seed=BIG_SEED,
                              seconds=seconds, trace=False, config=config,
                              mix=mix, log=lambda s: None)


CELLS = ["pokec.pagerank", "ml_laplace.serve_closed", "pokec.serve_open"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(bench, cell):
    res = _tiny_run(bench, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {
        m["name"] for m in bench_run.cell_metrics(bench, cell, False)}
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(bench, cell):
    res = _tiny_run(bench, cell, value_dtype="bfloat16")
    assert not res["correct"], res["checks"]


def _alter_one_answer(fn):
    def broken(*args, **kw):
        return fn(*args, **kw).at[7].add(1.0)
    return broken


def _drop_half_the_batch(fn):
    def broken(idx, val, seg, x_mat, **kw):
        return fn(idx, val, seg, x_mat.at[:, x_mat.shape[1] // 2:].set(0.0),
                  **kw)
    return broken


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_is_caught(bench, cell, monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "spmv_stream_xla",
                        _alter_one_answer(ops.spmv_stream_xla))
    monkeypatch.setattr(ops, "spmm_stream_xla",
                        _alter_one_answer(ops.spmm_stream_xla))
    assert not _tiny_run(bench, cell)["correct"]


@pytest.mark.parametrize("cell", CELLS[1:])
def test_half_a_batch_left_out_is_caught(bench, cell, monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "spmm_stream_xla",
                        _drop_half_the_batch(ops.spmm_stream_xla))
    # Open-loop arrivals fast enough that requests share batches.
    assert not _tiny_run(bench, cell, rate=1000.0)["correct"]


def test_a_step_that_keeps_its_state_is_caught(bench, monkeypatch):
    import importlib
    import jax.numpy as jnp
    power_iteration = importlib.import_module(
        "repro.solvers.power_iteration")

    def unchanged(acc2, r2, mask2, consts):
        return r2, jnp.zeros((1, 1), jnp.float32)

    monkeypatch.setattr(power_iteration, "_pagerank_epilogue", unchanged)
    res = _tiny_run(bench, "pokec.pagerank")
    assert not res["correct"]
    assert res["checks"]["pagerank_l1_gap"]["value"] > \
        res["checks"]["pagerank_l1_gap"]["limit"]


def test_no_chip_no_result(capsys):
    assert bench_run.main(["--workload", "pokec.pagerank", "--seed", "1",
                           "--seconds", "1"]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "TPU" in out.err
    assert math.isfinite(bench_run.T_PROCESS)
