"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload pokec.pagerank --seed 7 --seconds 40 \
        --trace 0

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``, read by ``bench/loadgen.py``).
A configuration holds:

* ``matrix``: the stand-in's generator (``bench/generators/<name>.py``)
  and its sizes; ``value_dtype``: the stream's value type;
* ``registry``: ``byte_budget`` and ``verify`` of ``MatrixRegistry``;
  ``service``: ``max_bucket`` of ``SpMVService``;
* ``limits``: the limit of each number the correctness check compares;
* optionally ``plan``: ``{"partition": "single"|"row"|"col"}`` (and
  ``lane_assign``), the ``PlanSpec`` that ``put`` encodes, with one shard
  for each of the cell's ``chips``.  Without it the matrix is put with
  the default plan.

A cell on more than one chip is served on a mesh of the first ``chips``
devices (axis ``chips``): ``registry.get`` binds the plan to it, or
repartitions the default plan by rows, and ``SpMVService`` serves on it,
as ``chip_smoke.serve_mesh`` does.  A ``solve`` mix runs there too:
``submit_solve`` binds the operator with the service's mesh, and the
solvers run a mesh-bound operator unfused, one sharded SpMV an iteration.

Device numbers (busy and idle time, the roofline's peak, peak memory)
are read over the cell's chips.  Each metric is a reader of its own,
``bench/end_to_end/<name>.py`` or ``bench/layer_metrics/<name>.py``,
found by the metric's name; a reader that finds nothing returns None and
the metric is left out.  A cell, mix, configuration or metric is added as
a file and an entry, with no file here edited.

Set-up (generate, ``put``, warm-up of every shape the mix uses) counts
into ``setup_s``; then the window runs for ``--seconds``.  Afterwards the
plain float64 reference (``bench/reference.py``) checks the answers the
window produced, and each compared number is printed beside its limit.
``--trace 1`` runs the window under the profiler and reports the
per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object.  Without a TPU, or
with fewer TPU chips than the cell's ``chips``, or outside a checkout
that holds ``src/repro``, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
REFERENCE_THREADS = 8       # after the window: the reference's own threads
MESH_AXIS = "chips"         # the mesh of a cell on more than one chip


def load_json(kind: str, name: str) -> dict:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


def load_reader(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones without a
    trace, per-layer ones with it."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in moved)]


def device_peaks(kind: str) -> dict:
    """Published peaks of one chip; a device not in the table is an error."""
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device_kind {kind!r}; known: "
                       f"{sorted(peaks)}")
    return peaks[kind]


@dataclasses.dataclass
class Run:
    """What the readers see of one run."""
    cell: str
    config: dict
    mix: dict
    seconds: float
    nnz: int                    # non-zeros of the matrix, from its triples
    shape: tuple
    value_dtype: str
    chips: int = 1              # the cell's devices
    gen_s: float = 0.0
    put_s: float = 0.0
    warm_s: float = 0.0
    setup_s: float = 0.0
    op_nnz: int = 0             # program counters of the encoded plan
    op_padded_slots: int = 0
    window: object = None       # loadgen.Window
    calls: int = 0              # device calls the service made in the window
    vectors: int = 0            # vectors they served
    spans: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None   # trace_reduce.reduce_profile of the window
    peaks: dict | None = None

    @property
    def window_s(self) -> float:
        return self.window.end - self.window.start

    @property
    def served(self) -> list:
        return [r for r in self.window.requests if r.error is None]

    def work(self) -> tuple[int, int]:
        """Counted ``(bytes, flops)`` of the window (``bench/work.py``):
        one pass per reported solver iteration, or the service's calls."""
        import work
        if self.mix["kind"] == "solve":
            iters = sum(r.solve.iterations for r in self.served)
            return work.window_work(self.nnz, self.shape, self.value_dtype,
                                    iters, iters)
        return work.window_work(self.nnz, self.shape, self.value_dtype,
                                self.calls, self.vectors)


def _pool(mix: dict, k: int, seed: int):
    import numpy as np
    if mix["kind"] == "solve":
        return None
    rng = np.random.default_rng([seed, 2])
    return rng.standard_normal((mix["pool"], k), dtype=np.float32)


def warm_up(svc, mid, mix: dict, pool) -> None:
    """Run every shape the mix uses once, synchronously: the solve itself,
    or each power-of-two batch width up to the service's ``max_bucket``
    (width 1 is the plain SpMV path)."""
    if mix["kind"] == "solve":
        svc.solve(mid, mix["solver"], **mix["params"])
        return
    width = 1
    while width <= svc.max_bucket:
        tickets = [svc.submit(mid, pool[j % len(pool)])
                   for j in range(width)]
        svc.flush()
        for t in tickets:
            svc.result(t, timeout=0)
        width *= 2


class _CompileCounter:
    """Counts XLA programs compiled or loaded from the cache while on."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.count += 1


def _mirrored_spans(obs, offset_ns: int) -> tuple[list, dict]:
    """The program's ``obs`` spans on the trace's clock, and their
    durations by name."""
    acts, durations = [], {}
    for buf in obs.TRACER.buffers():
        for ph, name, _, ts, dur, _, _ in list(buf.events):
            if ph != "X":
                continue
            acts.append((f"repro.{name}", ts + offset_ns,
                         ts + dur + offset_ns))
            durations.setdefault(name, []).append(dur / 1e9)
    return acts, durations


def check(run: Run, rows, cols, vals, pool, log) -> dict:
    """Each compared number with its limit (``bench/reference.py``)."""
    import reference
    limits = run.config["limits"]
    reqs = run.window.requests
    checks = {"missing": {"value": sum(r.error is not None for r in reqs),
                          "limit": 0}}
    t0 = time.perf_counter()
    with reference.Reference(rows, cols, vals, run.shape,
                             threads=REFERENCE_THREADS) as ref:
        if run.mix["kind"] == "solve":
            r_star = ref.pagerank(run.mix["params"]["damping"])
            gap = max((reference.l1_gap(r.y, r_star) for r in run.served),
                      default=math.inf)
            checks["pagerank_l1_gap"] = {"value": gap,
                                         "limit": limits["pagerank_l1_gap"]}
        else:
            used = sorted({r.vector for r in run.served})
            gap = 0.0 if used else math.inf
            for i in range(0, len(used), 16):       # blocks of 16 vectors
                block = used[i:i + 16]
                ys, mags = ref.products(pool[block])
                at = {j: n for n, j in enumerate(block)}
                gap = max([gap, *ref.rel_gaps(
                    (r.y, ys[at[r.vector]], mags[at[r.vector]])
                    for r in run.served if r.vector in at)])
            checks["spmv_rel_gap"] = {"value": gap,
                                      "limit": limits["spmv_rel_gap"]}
    log(f"reference_s={time.perf_counter() - t0:.3f} "
        f"compared={len(run.served)}")
    return checks


def run_cell(bench: dict, cell_name: str, *, seed: int, seconds: float,
             trace: bool, t_process: float = T_PROCESS, log=print,
             config: dict | None = None, mix: dict | None = None) -> dict:
    """Set up, drive and check one cell; returns the result object.

    Takes no notice of the platform: the caller checks for the chip.
    ``config`` and ``mix`` stand in for the cell's files (tests)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro import obs
    from repro.core.partition import PlanSpec
    from repro.core.registry import MatrixRegistry
    from repro.serve.spmv_service import SpMVService
    import loadgen

    cell = next(c for c in bench["workloads"] if c["name"] == cell_name)
    config = config or load_json("configs", cell["config"])
    mix = mix or load_json("traffic", cell["traffic"])
    devices = jax.devices()[:cell["chips"]]
    if len(devices) < cell["chips"]:
        raise ValueError(f"{cell_name} needs {cell['chips']} devices; "
                         f"JAX has {len(devices)}")
    plan = config.get("plan")
    put_kw = ({"spec": PlanSpec(num_shards=len(devices), **plan)}
              if plan else {})
    bind_kw = {}
    if len(devices) > 1:
        bind_kw = {"mesh": Mesh(np.array(devices), (MESH_AXIS,)),
                   "axis": MESH_AXIS,
                   "partition": plan["partition"] if plan else None}
    dev = devices[0]

    t = time.perf_counter()
    gen = load_reader("generators", config["matrix"]["generator"])
    rows, cols, vals, shape = gen.generate(config["matrix"], seed)
    pool = _pool(mix, shape[1], seed)
    run = Run(cell=cell_name, config=config, mix=mix, seconds=seconds,
              nnz=int(rows.size), shape=tuple(shape),
              value_dtype=config["value_dtype"], chips=len(devices),
              gen_s=time.perf_counter() - t)
    log(f"setup generate_s={run.gen_s:.3f} nnz={run.nnz} shape={shape}")

    reg_cfg = config["registry"]
    reg = MatrixRegistry(byte_budget=int(reg_cfg["byte_budget"]),
                         verify=reg_cfg["verify"])
    t = time.perf_counter()
    mid = reg.put(rows, cols, vals, shape, value_dtype=run.value_dtype,
                  **put_kw)
    op = reg.get(mid, **bind_kw)
    run.put_s = time.perf_counter() - t
    run.op_nnz, run.op_padded_slots = int(op.nnz), int(op.padded_slots)
    log(f"setup put_s={run.put_s:.3f} slots={run.op_padded_slots} "
        f"stream_bytes={op.stream_bytes} plan={op.plan.spec.partition}:"
        f"{op.plan.num_shards} stream_devices={len(op.stream_devices)}")
    del op

    svc = SpMVService(reg, max_bucket=int(config["service"]["max_bucket"]),
                      **bind_kw)
    compiles = _CompileCounter()
    t = time.perf_counter()
    compiles.on = True
    warm_up(svc, mid, mix, pool)
    compiles.on = False
    run.warm_s = time.perf_counter() - t
    log(f"setup warm_s={run.warm_s:.3f} programs_compiled_or_loaded="
        f"{compiles.count}")
    compiles.count = 0
    if mix["kind"] != "solve":
        svc.start()
    stats0 = svc.stats
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        perf_sync = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("bench.sync"):
            pass
        obs.clear()
        obs.enable()
    run.setup_s = time.perf_counter() - t_process
    log(f"setup_s={run.setup_s:.3f}")

    compiles.on = True
    with (jax.profiler.TraceAnnotation("bench.window") if trace
          else contextlib.nullcontext()):
        run.window = loadgen.run(svc, mid, mix, seconds, pool, seed,
                                 ann=trace)
    compiles.on = False
    svc.stop()
    stats1 = svc.stats
    run.calls = stats1.batches - stats0.batches
    run.vectors = stats1.vectors - stats0.vectors
    log(f"window_s={run.window_s:.3f} requests={len(run.window.requests)} "
        f"calls={run.calls} vectors={run.vectors} "
        f"window_compiles={compiles.count}")
    if mix["kind"] != "solve":
        lat = [(r.done - r.due) * 1e3 for r in run.served]
        log("latency_ms " + " ".join(
            f"p{p}={loadgen.percentile(lat, p):.1f}" for p in (50, 80, 90, 100)))
    if trace:
        obs.disable()
        jax.profiler.stop_trace()
        import trace_reduce
        pb = next(TRACE_DIR.rglob("*.xplane.pb"))
        profile = trace_reduce.load(pb)
        offset = trace_reduce.clock_offset_ns(
            trace_reduce.host_events(profile), perf_sync)
        acts, run.spans = _mirrored_spans(obs, offset)
        obs.clear()
        run.trace = trace_reduce.reduce_profile(
            profile, extra_host=acts, device_ids=[d.id for d in devices])
        run.peaks = device_peaks(dev.device_kind)
        log(f"busy_s_by_device={run.trace['busy_s_by_device']} "
            f"device_ids={run.trace['device_ids']}")
        log(f"idle_by_activity={json.dumps(run.trace['idle_by_activity'])}")
    peak_bytes = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devices]
    log(f"memory_peak_bytes_by_device={peak_bytes}")

    reg.close()
    del svc, reg
    gc.collect()
    checks = check(run, rows, cols, vals, pool, log)

    metrics = {}
    for m in cell_metrics(bench, cell_name, trace):
        kind = "layer_metrics" if trace else "end_to_end"
        value = load_reader(kind, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "chips": run.chips,
              "memory_peak_bytes": max(peak_bytes)}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for c in checks.values():       # JSON has no inf: a missing number
        if not math.isfinite(c["value"]):
            c["value"] = None
    result = {"correct": correct,
              "attempted": len(run.window.requests),
              "failed": checks["missing"]["value"],
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench/run.py: no src/repro next to {BENCH}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    bench = load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"bench/run.py: unknown workload {args.workload!r}; known: "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # not /tmp/tpu_logs
    import jax
    devices = jax.devices()
    chips = cells[args.workload]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench/run.py: {args.workload} needs {chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{CACHE_DIR}", flush=True)
    result = run_cell(bench, args.workload, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      log=lambda s: print(s, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
