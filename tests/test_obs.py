"""Observability substrate: tracer, metrics, Chrome-trace export."""
import json
import threading

import pytest

from repro import obs
from repro.obs.export import (export_chrome_trace, validate_chrome_trace,
                              write_chrome_trace)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry)
from repro.obs.trace import Tracer


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with the global tracer off and empty."""
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


# -- tracing ----------------------------------------------------------------

class TestTracer:
    def test_disabled_by_default_records_nothing(self):
        with obs.span("x", a=1) as sp:
            sp.args["b"] = 2
        obs.instant("y")
        obs.flow_start("f", 1)
        assert obs.TRACER.event_count() == 0

    def test_disabled_span_is_shared_noop(self):
        s1 = obs.span("a")
        s2 = obs.span("b", k=1)
        assert s1 is s2                 # no allocation on the cold path

    def test_span_records_duration_and_args(self):
        obs.enable()
        with obs.span("work", matrix="m1") as sp:
            sp.args["late"] = 7
        bufs = obs.TRACER.buffers()
        assert len(bufs) == 1
        ph, name, cat, ts, dur, args, fid = bufs[0].events[0]
        assert ph == "X" and name == "work" and dur >= 0
        assert args == {"matrix": "m1", "late": 7}

    def test_span_emits_even_when_body_raises(self):
        obs.enable()
        with pytest.raises(RuntimeError):
            with obs.span("boom"):
                raise RuntimeError("x")
        assert obs.TRACER.event_count() == 1

    def test_event_records_explicit_duration(self):
        obs.enable()
        obs.event("shipped", 0.5, range=3)
        (ph, name, _, _, dur, args, _), = obs.TRACER.buffers()[0].events
        assert ph == "X" and dur == int(0.5e9) and args == {"range": 3}

    def test_flow_events(self):
        obs.enable()
        obs.flow_start("req", 42)
        obs.flow_step("req", 42)
        obs.flow_end("req", 42)
        phases = [e[0] for e in obs.TRACER.buffers()[0].events]
        fids = {e[6] for e in obs.TRACER.buffers()[0].events}
        assert phases == ["s", "t", "f"] and fids == {42}

    def test_ring_overflow_drops_oldest_and_counts(self):
        tr = Tracer(max_events_per_thread=8)
        tr.enable()
        for i in range(20):
            tr.instant(f"e{i}")
        buf = tr.buffers()[0]
        assert len(buf.events) == 8
        assert buf.dropped == 12
        assert buf.events[0][1] == "e12"    # oldest kept

    def test_per_thread_buffers(self):
        obs.enable()

        def work():
            obs.instant("from-thread")

        t = threading.Thread(target=work, name="worker-1")
        t.start()
        t.join()
        obs.instant("from-main")
        names = {b.thread_name for b in obs.TRACER.buffers()}
        assert "worker-1" in names and len(obs.TRACER.buffers()) == 2

    def test_clear_resets_buffers_and_epoch(self):
        obs.enable()
        obs.instant("x")
        assert obs.TRACER.event_count() == 1
        obs.clear()
        assert obs.TRACER.event_count() == 0
        obs.instant("y")                # stale tls buffer must re-register
        assert obs.TRACER.event_count() == 1

    def test_context_inheritance_across_threads(self):
        obs.enable()
        with obs.attach_context({}, request="r9"):
            ctx = obs.capture_context()

        def work():
            with obs.attach_context(ctx, worker=1):
                obs.instant("inside")
            obs.instant("outside")

        t = threading.Thread(target=work)
        t.start()
        t.join()
        buf = next(b for b in obs.TRACER.buffers()
                   if any(e[1] == "inside" for e in b.events))
        by_name = {e[1]: e[5] for e in buf.events}
        assert by_name["inside"] == {"request": "r9", "worker": 1}
        assert by_name["outside"] is None

    def test_attach_context_nests_and_restores(self):
        obs.enable()
        with obs.attach_context({"a": 1}):
            with obs.attach_context({"b": 2}):
                assert obs.capture_context() == {"a": 1, "b": 2}
            assert obs.capture_context() == {"a": 1}
        assert obs.capture_context() == {}


# -- export -----------------------------------------------------------------

class TestExport:
    def test_export_schema_and_metadata(self, tmp_path):
        obs.enable()
        with obs.span("s", k="v"):
            pass
        obs.instant("i")
        obs.flow_start("req", 7)
        obs.flow_end("req", 7)
        path = tmp_path / "t.json"
        doc = write_chrome_trace(str(path))
        validate_chrome_trace(doc)
        on_disk = json.loads(path.read_text())
        assert on_disk == doc
        evs = doc["traceEvents"]
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in evs)
        x = next(e for e in evs if e["ph"] == "X")
        assert x["name"] == "s" and x["args"] == {"k": "v"} \
            and x["dur"] >= 0
        f = next(e for e in evs if e["ph"] == "f")
        assert f["id"] == 7 and f["bp"] == "e"

    def test_export_reports_drops_in_thread_metadata(self):
        tr = Tracer(max_events_per_thread=4)
        tr.enable()
        for i in range(10):
            tr.instant(f"e{i}")
        doc = export_chrome_trace(tr)
        meta = next(e for e in doc["traceEvents"]
                    if e["name"] == "thread_name")
        assert meta["args"]["dropped_events"] == 6

    @pytest.mark.parametrize("bad", [
        [],                                            # not a dict
        {"traceEvents": {}},                           # not a list
        {"traceEvents": [{"ph": "Z", "name": "x",
                          "pid": 1, "tid": 1, "ts": 0}]},   # bad phase
        {"traceEvents": [{"ph": "X", "name": "",
                          "pid": 1, "tid": 1, "ts": 0,
                          "dur": 1}]},                 # empty name
        {"traceEvents": [{"ph": "X", "name": "x",
                          "pid": 1, "tid": 1, "ts": 0}]},   # X w/o dur
        {"traceEvents": [{"ph": "s", "name": "x",
                          "pid": 1, "tid": 1, "ts": 0}]},   # flow w/o id
    ])
    def test_validate_rejects_malformed(self, bad):
        with pytest.raises(ValueError):
            validate_chrome_trace(bad)


# -- metrics ----------------------------------------------------------------

class TestCounterGauge:
    def test_counter_inc_add_and_labels(self):
        c = Counter("reqs")
        c.inc()
        c.add(2.5)
        c.inc(owner="a")
        c.inc(owner="a")
        c.inc(owner="b")
        assert c.value() == 3.5
        assert c.value(owner="a") == 2
        assert c.total() == 6.5

    def test_counter_negative_add_rolls_back(self):
        c = Counter("work")
        c.add(5)
        c.add(-3)           # the service's flush-failure rollback path
        assert c.value() == 2

    def test_gauge_set_and_add(self):
        g = Gauge("depth")
        g.set(4)
        g.add(-1)
        assert g.value() == 3

    def test_invalid_metric_name(self):
        with pytest.raises(ValueError):
            Counter("bad name!")


class TestHistogram:
    def test_boundary_value_lands_in_le_bucket(self):
        h = Histogram("h", buckets=(0.001, 0.01, 0.1))
        for v in (0.001, 0.0005, 0.01, 0.05, 0.5):
            h.observe(v)
        # le-inclusive: 0.001 and 0.0005 in the first bucket, 0.01 in the
        # second, 0.05 in the third, 0.5 overflows.
        assert h.bucket_counts() == [2, 1, 1, 1]

    def test_exact_percentiles_nearest_rank(self):
        h = Histogram("h", buckets=(1.0,))
        for v in range(1, 101):      # 1..100
            h.observe(float(v))
        assert h.percentile(50) == 50.0
        assert h.percentile(95) == 95.0
        assert h.percentile(99) == 99.0
        assert h.percentile(100) == 100.0
        assert h.percentile(1) == 1.0

    def test_percentile_empty_and_bad_p(self):
        h = Histogram("h")
        assert h.percentile(50) == 0.0
        with pytest.raises(ValueError):
            h.percentile(0)
        with pytest.raises(ValueError):
            h.percentile(101)

    def test_bucket_percentile_interpolates_and_clamps(self):
        h = Histogram("h", buckets=(1.0, 2.0), max_samples=0)
        for _ in range(10):
            h.observe(1.5)           # all in the (1.0, 2.0] bucket
        assert h.percentile(50) == pytest.approx(1.5)   # falls back
        h.observe(5.0)               # overflow clamps to last bound
        assert h.bucket_percentile(100) == 2.0

    def test_sample_window_bounds_memory(self):
        h = Histogram("h", buckets=(1.0,), max_samples=4)
        for v in (1, 2, 3, 4, 5, 6):
            h.observe(float(v))
        assert h.count == 6
        assert h.percentile(100) == 6.0     # window keeps 3,4,5,6
        assert h.percentile(1) == 3.0

    def test_rejects_bad_buckets(self):
        with pytest.raises(ValueError):
            Histogram("h", buckets=())
        with pytest.raises(ValueError):
            Histogram("h", buckets=(2.0, 1.0))


class TestRegistry:
    def test_get_or_create_returns_same_instance(self):
        r = MetricsRegistry()
        a = r.counter("x")
        b = r.counter("x")
        assert a is b

    def test_type_conflict_raises(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(ValueError):
            r.histogram("x")

    def test_snapshot_shape(self):
        r = MetricsRegistry()
        r.counter("c").inc(3)
        r.histogram("h").observe(0.01)
        snap = r.snapshot()
        assert snap["c"]["total"] == 3
        assert snap["h"]["count"] == 1 and snap["h"]["p50"] == 0.01

    def test_prometheus_text_exposition(self):
        r = MetricsRegistry()
        r.counter("reqs", "requests").inc(2, owner="a")
        h = r.histogram("lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = r.prometheus_text()
        assert "# TYPE reqs counter" in text
        assert 'reqs{owner="a"} 2' in text
        assert 'lat_bucket{le="0.1"} 1' in text
        assert 'lat_bucket{le="1"} 2' in text
        assert 'lat_bucket{le="+Inf"} 2' in text
        assert "lat_count 2" in text

    def test_label_escaping(self):
        r = MetricsRegistry()
        r.counter("c").inc(owner='we"ird\\name')
        assert '\\"' in r.prometheus_text()


def test_obs_package_does_not_import_jax():
    """obs must stay importable from numpy-only encode workers."""
    import subprocess
    import sys
    code = ("import sys; import repro.obs; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run([sys.executable, "-c", code])
    assert proc.returncode == 0


def test_cost_report_measure_needs_a_known_device():
    """The cost report is counted from the plan alone, on any device: it
    times nothing and looks up no peak (the benchmark times the chip)."""
    import numpy as np
    from repro.core.spmv import SerpensSpMV
    rng = np.random.default_rng(0)
    rows, cols = rng.integers(0, 32, 200), rng.integers(0, 32, 200)
    op = SerpensSpMV(rows, cols, rng.normal(size=200).astype(np.float32),
                     (32, 32), backend="xla")
    rep = op.cost_report()
    assert rep["stream_bytes"] == op.stream_bytes > 0
    assert rep["padded_slots"] == op.padded_slots
    assert rep["bytes_per_slot"] == 8 and rep["value_dtype"] == "float32"
    assert not {"device_kind", "measured_matvec_s", "achieved_gbps",
                "roofline_fraction"} & set(rep)
    with pytest.raises(TypeError):
        op.cost_report(measure=True)
