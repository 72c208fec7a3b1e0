"""Spans the program records where its time goes: request waits in the
pipelined service (``queue-wait``, ``inflight``), a solve's host and
device parts (``solver-init``, ``solver-launch``, ``solver-wait``) and
JAX's compile events (``jax-*``).  The benchmark's readers turn them into
per-layer metrics, so their order and counts are checked here; sums of
durations are not, since parallel test workers stretch them."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, solvers
from repro.core import format as F
from repro.core.registry import MatrixRegistry
from repro.core.spmv import SerpensSpMV, from_dense
from repro.data import matrices as M
from repro.obs import profile as obs_profile
from repro.serve.spmv_service import SpMVService

CFG = F.SerpensConfig(segment_width=512, lanes=16, sublanes=8)
SOLVER_CFG = F.SerpensConfig(segment_width=64, lanes=8, sublanes=4,
                             raw_window=4)
MS = 1_000_000                  # ns
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(autouse=True)
def _clean_tracer():
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def _spans(name):
    """``(start_ns, end_ns, args)`` of every recorded span called
    ``name``, by start."""
    out = []
    for buf in obs.TRACER.buffers():
        for ph, ev, _, ts, dur, args, _ in list(buf.events):
            if ph == "X" and ev == name:
                out.append((ts, ts + dur, args or {}))
    return sorted(out, key=lambda s: s[:2])


def _serve_pipelined(clients=4, per_client=6, n=256):
    """Closed-loop clients on a started service; returns each ticket's
    ``(before_submit, after_submit, completed)`` in ns and the stats."""
    rows, cols, vals = M.uniform_random(n, n, 2_000, seed=0)
    reg = MatrixRegistry(config=CFG, backend="xla")
    mid = reg.put(rows, cols, vals, (n, n))
    svc = SpMVService(reg, backend="xla", max_bucket=4)
    times, lock = {}, threading.Lock()

    def client(c):
        rng = np.random.default_rng(c)
        for _ in range(per_client):
            x = rng.standard_normal(n).astype(np.float32)
            t0 = time.perf_counter_ns()
            ticket = svc.submit(mid, x, owner=f"c{c}")
            t1 = time.perf_counter_ns()
            svc.result(ticket, timeout=60)
            with lock:
                times[ticket] = (t0, t1, time.perf_counter_ns())

    svc.start()
    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        svc.stop()
        reg.close()
    assert len(times) == clients * per_client
    return times, svc.stats


def test_one_queue_wait_per_request_and_one_inflight_per_batch():
    obs.enable()
    times, stats = _serve_pipelined()
    obs.disable()
    waits = _spans("queue-wait")
    assert sorted(a["ticket"] for _, _, a in waits) == sorted(times)
    flights = _spans("inflight")
    assert len(flights) == stats.batches
    assert sum(a["batch"] for _, _, a in flights) == len(times)


def test_request_spans_come_in_order():
    """Per request: submit -> queue-wait -> dispatch -> inflight -> done.
    A batch holds consecutive tickets from its first (one matrix, FIFO
    queue), and the k-th dispatch launches the k-th inflight batch."""
    obs.enable()
    times, _ = _serve_pipelined()
    obs.disable()
    waits = {a["ticket"]: (t0, t1) for t0, t1, a in _spans("queue-wait")}
    dispatches = sorted(_spans("dispatch"), key=lambda s: s[1])
    flights = _spans("inflight")
    assert len(dispatches) == len(flights)
    seen = set()
    for (d0, d1, d_args), (f0, f1, f_args) in zip(dispatches, flights):
        assert d_args["batch"] == f_args["batch"]
        assert abs(f0 - d1) <= MS
        for ticket in range(f_args["ticket"],
                            f_args["ticket"] + f_args["batch"]):
            before, after, done = times[ticket]
            q0, q1 = waits[ticket]
            assert before - MS <= q0 <= after + MS
            assert q1 <= d0 + MS
            assert f1 <= done + MS
            seen.add(ticket)
    assert seen == set(times)


def test_tracing_off_records_nothing():
    times, stats = _serve_pipelined(clients=2, per_client=3)
    jax.jit(lambda v: v * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
    assert stats.batches > 0 and len(times) == 6
    assert obs.TRACER.event_count() == 0


def _pagerank_op():
    n = 120
    rows, cols, vals = M.power_law_graph(n, 900, seed=1)
    vals = M.column_normalize(rows, cols, vals, n)
    return SerpensSpMV(rows, cols, vals, (n, n), SOLVER_CFG)


def _spd_op():
    rng = np.random.default_rng(0)
    n = 64
    a = np.zeros((n, n), np.float32)
    idx = rng.integers(0, n, (4 * n, 2))
    a[idx[:, 0], idx[:, 1]] = rng.normal(size=4 * n)
    a = (a + a.T) / 2
    a[np.arange(n), np.arange(n)] = np.abs(a).sum(1) + 1.0
    return from_dense(a, SOLVER_CFG)


SOLVES = {
    "pagerank": (_pagerank_op, {"tol": 1e-6}),
    "power-iteration": (_pagerank_op, {"tol": 1e-5, "max_iters": 300}),
    "conjugate-gradient": (_spd_op, {"b": np.ones(64, np.float32)}),
}
KINDS = {"pagerank": "pagerank", "power-iteration": "power_iteration",
         "conjugate-gradient": "conjugate_gradient"}


@pytest.mark.parametrize("span", list(SOLVES))
def test_solver_spans_split_init_launch_and_wait(span):
    """The three children lie in order, apart, inside the solver's span;
    the rebuilt loop's compiles lie inside the launch, one span for each
    compile a separate listener hears."""
    make_op, kw = SOLVES[span]
    op = make_op()
    solvers.solve(op, KINDS[span], **kw)    # compiles init and read-back
    heard = []

    def hear(event, duration, **_):
        if event == BACKEND_COMPILE:
            heard.append(duration)

    jax.monitoring.register_event_duration_secs_listener(hear)
    try:
        obs.enable()
        solvers.solve(op, KINDS[span], **kw)
        obs.disable()
    finally:
        jax.monitoring.unregister_event_duration_listener(hear)
    (outer,) = _spans(span)
    (init,), (launch,), (wait,) = (_spans(f"solver-{part}")
                                   for part in ("init", "launch", "wait"))
    edges = [outer[0], init[0], init[1], launch[0], launch[1], wait[0],
             wait[1], outer[1]]
    assert edges == sorted(edges)
    compiles = _spans("jax-compile")
    assert len(compiles) == len(heard) >= 1
    assert all(launch[0] <= c0 and c1 <= launch[1] for c0, c1, _ in compiles)
    assert all(c_args["fun"] for _, _, c_args in compiles)


def test_compile_spans_installer_is_idempotent():
    for _ in range(3):
        obs_profile.install_compile_spans()
    heard = []

    def hear(event, duration, **_):
        if event in obs_profile.COMPILE_SPANS:
            heard.append(obs_profile.COMPILE_SPANS[event])

    jax.monitoring.register_event_duration_secs_listener(hear)
    try:
        obs.enable()
        jax.jit(lambda v: jnp.sin(v) * 5.0 - 2.0)(jnp.ones(13))
        obs.disable()
    finally:
        jax.monitoring.unregister_event_duration_listener(hear)
    assert heard.count("jax-compile") >= 1
    recorded = [name for name in obs_profile.COMPILE_SPANS.values()
                for _ in _spans(name)]
    assert sorted(recorded) == sorted(heard)
