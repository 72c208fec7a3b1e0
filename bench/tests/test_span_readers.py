"""The per-layer metrics read from the program's own spans: each reader
on hand-made spans, its silence where the spans or the traffic kind do
not apply, and a traced run at CPU size whose result line carries them."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import run as bench_run  # noqa: E402

SOLVE = {"kind": "solve", "solver": "pagerank"}
OPEN = {"kind": "open"}
CLOSED = {"kind": "closed"}
TENTHS = [i / 10 for i in range(10, 0, -1)]          # 1.0 s .. 0.1 s
NEW = {"solver.host_ms_per_solve": "pokec.pagerank",
       "solver.compiles_per_solve": "pokec.pagerank",
       "serve.queue_wait_ms.p80": "pokec.serve_open",
       "serve.inflight_ms.p80": "pokec.serve_open",
       "serve.queue_wait_ms.mteps": "ml_laplace.serve_closed"}


def _read(name, mix, spans):
    run = bench_run.Run(cell="c", config={}, mix=mix, seconds=1.0, nnz=1,
                        shape=(1, 1), value_dtype="float32", spans=spans)
    return bench_run.load_reader("layer_metrics", name).read(run)


@pytest.mark.parametrize("name, mix, spans, value", [
    # (5.0 + 5.2 - 4.9 - 5.05) s over two solves.
    ("solver.host_ms_per_solve", SOLVE,
     {"pagerank": [5.0, 5.2], "solver-wait": [4.9, 5.05],
      "solver-launch": [0.09, 0.14]}, 125.0),
    ("solver.host_ms_per_solve",
     {"kind": "solve", "solver": "conjugate_gradient"},
     {"conjugate-gradient": [2.0], "solver-wait": [1.5],
      "pagerank": [9.0]}, 500.0),
    ("solver.compiles_per_solve", SOLVE,
     {"solver-wait": [1.0] * 4, "jax-compile": [0.05] * 6}, 1.5),
    ("solver.compiles_per_solve", SOLVE,
     {"solver-wait": [1.0] * 4, "jax-trace": [0.01] * 4}, 0.0),
    # Nearest rank: the 8th smallest of ten, and the 4th of five.
    ("serve.queue_wait_ms.p80", OPEN, {"queue-wait": TENTHS}, 800.0),
    ("serve.inflight_ms.p80", OPEN, {"inflight": TENTHS[5:]}, 400.0),
    ("serve.queue_wait_ms.mteps", CLOSED,
     {"queue-wait": [1.0, 2.0, 3.0, 6.0]}, 3000.0),
])
def test_reader_gives_the_hand_computed_value(name, mix, spans, value):
    assert _read(name, mix, spans) == pytest.approx(value)


@pytest.mark.parametrize("name, mix, spans", [
    # The parent's spans: no solver children, no request waits.
    ("solver.host_ms_per_solve", SOLVE, {"pagerank": [5.0]}),
    ("solver.compiles_per_solve", SOLVE, {"pagerank": [5.0]}),
    ("serve.queue_wait_ms.p80", OPEN, {"dispatch": [0.01]}),
    ("serve.inflight_ms.p80", OPEN, {"device-block": [0.5]}),
    ("serve.queue_wait_ms.mteps", CLOSED, {}),
    # A mix of the other kind.
    ("solver.host_ms_per_solve", OPEN,
     {"pagerank": [5.0], "solver-wait": [4.0]}),
    ("solver.compiles_per_solve", CLOSED,
     {"solver-wait": [4.0], "jax-compile": [0.1]}),
    ("serve.queue_wait_ms.p80", SOLVE, {"queue-wait": [0.1]}),
    ("serve.inflight_ms.p80", SOLVE, {"inflight": [0.1]}),
    ("serve.queue_wait_ms.mteps", SOLVE, {"queue-wait": [0.1]}),
])
def test_reader_is_silent_where_it_does_not_apply(name, mix, spans):
    assert _read(name, mix, spans) is None


def test_new_metrics_are_listed_for_their_cells():
    bench = bench_run.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name, cell in NEW.items():
        m = per_layer[name]
        assert m["workloads"] == [cell] and m["source"] == "program_span"
        assert m in bench_run.cell_metrics(bench, cell, True)


TINY = {"pokec": {"rows": 3000, "cols": 3000, "edges": 30000},
        "ml_laplace": {"rows": 3000, "cols": 3000}}


@pytest.mark.parametrize("cell", sorted(set(NEW.values())))
def test_traced_run_reports_the_span_metrics(cell, monkeypatch):
    """The whole ``--trace 1`` path at CPU size, with the device-trace
    reduction stubbed (the CPU trace has no TPU plane): the names the
    program records are the names the readers look up."""
    import trace_reduce

    def no_device(profile, extra_host=(), device_ids=None):
        assert any(name.startswith("repro.") for name, _, _ in extra_host)
        assert len(device_ids) == 1
        return {"busy_s": 1.0, "busy_s_by_device": [1.0],
                "device_ids": device_ids, "window_s": 2.0, "devices": 1,
                "device_ops": [], "idle_gaps": [], "idle_by_activity": {}}

    monkeypatch.setattr(trace_reduce, "reduce_profile", no_device)
    monkeypatch.setattr(bench_run, "device_peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    bench = bench_run.load_benchmark()
    spec = next(c for c in bench["workloads"] if c["name"] == cell)
    config = bench_run.load_json("configs", spec["config"])
    config["matrix"].update(TINY[spec["config"]])
    mix = bench_run.load_json("traffic", spec["traffic"])
    if mix["kind"] == "open":
        mix["rate"] = 20.0
    res = bench_run.run_cell(bench, cell, seed=2**31 + 977, seconds=1.0,
                             trace=True, config=config, mix=mix,
                             log=lambda s: None)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    for name in (n for n, c in NEW.items() if c == cell):
        assert got[name]["value"] >= 0, name
    if cell == "pokec.pagerank":
        assert got["solver.compiles_per_solve"]["value"] >= 1
        assert got["solver.host_ms_per_solve"]["value"] > 0
