"""Cells whose configuration names a plan, on the CPU.

``run_cell`` drives a tiny ``pokec`` configuration in a subprocess that
has four virtual CPU devices (``--xla_force_host_platform_device_count=4``,
as ``tests/test_distributed.py`` does): a row plan on a 4-chip cell under
the ``serve_closed`` and ``pagerank`` mixes, a 4-chip cell with no plan,
and plans on a 1-chip cell.  Each cell is added to an in-memory copy of
``BENCHMARK.json`` only.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

ROW, COL = {"partition": "row"}, {"partition": "col"}
CASES = {       # case: traffic, the cell's chips, plan, value dtype
    "serve_closed.float32": ("serve_closed", 4, ROW, "float32"),
    "serve_closed.bfloat16": ("serve_closed", 4, ROW, "bfloat16"),
    "pagerank.float32": ("pagerank", 4, ROW, "float32"),
    "pagerank.bfloat16": ("pagerank", 4, ROW, "bfloat16"),
    "no_plan": ("serve_closed", 4, None, "float32"),
    "row_on_one": ("serve_closed", 1, ROW, "float32"),
    "col_on_one": ("serve_closed", 1, COL, "float32"),
}
E2E = {"serve_closed": "mteps", "pagerank": "solve_s"}

SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1] + "/bench"]
import run as bench_run
from repro.core.registry import MatrixRegistry

bound = []
get = MatrixRegistry.get
def recording_get(self, *args, **kw):
    op = get(self, *args, **kw)
    bound.append([op.plan.spec.partition, op.plan.num_shards,
                  None if op.mesh is None else op.mesh.size,
                  len(op.stream_devices)])
    return op
MatrixRegistry.get = recording_get

cases, e2e = json.loads(sys.argv[2]), json.loads(sys.argv[3])
bench = bench_run.load_benchmark()
config = bench_run.load_json("configs", "pokec")
config["matrix"].update(rows=3000, cols=3000, edges=30000)
out = {}
for case, (traffic, chips, plan, dtype) in cases.items():
    name = "pokec." + case
    bench["workloads"].append({"name": name, "config": "pokec",
                               "traffic": traffic, "chips": chips})
    next(m for m in bench["end_to_end"]
         if m["name"] == e2e[traffic])["workloads"].append(name)
    cfg = dict(config, value_dtype=dtype)
    if plan:
        cfg["plan"] = plan
    bound.clear()
    res = bench_run.run_cell(
        bench, name, seed=2**31 + 977, seconds=1.0, trace=False,
        config=cfg, mix=bench_run.load_json("traffic", traffic),
        log=lambda s: None)
    want = {m["name"] for m in bench_run.cell_metrics(bench, name, False)}
    out[case] = {"result": res, "want": sorted(want), "bound": bound[:]}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(ROOT), json.dumps(CASES),
         json.dumps(E2E)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = next(s for s in proc.stdout.splitlines()
                if s.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def _sound(run, chips):
    res = run["result"]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert sorted(res["metrics"]) == run["want"]
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["chips"] == chips and res["device"]["count"] == 4


@pytest.mark.parametrize("traffic", ["serve_closed", "pagerank"])
def test_row_plan_on_four_devices_is_correct(runs, traffic):
    run = runs[f"{traffic}.float32"]
    _sound(run, 4)
    # Set-up's get and every get of the service: the 4-shard mesh binding.
    assert run["bound"] and all(b == ["row", 4, 4, 4] for b in run["bound"])


@pytest.mark.parametrize("traffic", ["serve_closed", "pagerank"])
def test_bfloat16_control_on_four_devices_is_not_correct(runs, traffic):
    res = runs[f"{traffic}.bfloat16"]["result"]
    assert not res["correct"], res["checks"]


def test_four_chip_cell_without_a_plan_is_served_by_rows(runs):
    """The default plan, put on one shard, is repartitioned by rows over
    the cell's four chips."""
    run = runs["no_plan"]
    _sound(run, 4)
    assert run["bound"] and all(b == ["row", 4, 4, 4] for b in run["bound"])


@pytest.mark.parametrize("partition", ["row", "col"])
def test_plan_on_one_chip_has_one_shard_and_no_mesh(runs, partition):
    run = runs[f"{partition}_on_one"]
    _sound(run, 1)
    assert run["bound"] and all(b == [partition, 1, None, 1]
                                for b in run["bound"])
