"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

A mix is data.  ``kind`` picks how the load is offered:

* ``"solve"``: one client runs back-to-back ``SpMVService.solve`` calls
  (``solver``, ``params``) until the first solve to finish after the
  window's length.
* ``"closed"``: ``clients`` threads each submit one vector, wait for its
  result and submit the next, until the window's length has passed;
  the requests in flight then drain.
* ``"open"``: requests are due on a fixed schedule (``arrivals``:
  ``"poisson"`` or ``"onoff"`` with ``on_s``/``off_s``) at ``rate`` per
  second, sent from one thread round-robin for ``owners`` owners,
  whatever the service does; each owner's results are collected by a
  thread of its own.  Latency runs from the due time.

Vectors come from a pool of ``pool`` vectors drawn from the run's seed.
The open-loop schedule comes from the mix's own ``schedule_seed``: every
run replays the same due times, whatever its seed, because near
capacity the order of the gaps alone moves the tail several-fold (two
orders of one set of gaps read 1.0 s and 4.4 s at the 80th percentile
on one v5e), and the seed must change the inputs, not the work.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import queue
import threading
import time

import numpy as np

RESULT_TIMEOUT_S = 600.0        # an answer that never comes, not a late one


def percentile(xs, p):
    """Nearest-rank percentile."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return float(xs[rank - 1])


def _stratified_gaps(n: int, rate: float, rng) -> np.ndarray:
    """``n`` exponential gaps at ``rate``: the quantiles at
    ``(i + 1/2) / n``, in a seeded order."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    return gaps


def poisson_arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """Due offsets of ``round(rate * seconds)`` requests with exponential
    gaps, scaled so the last one is due at ``seconds``."""
    n = max(1, round(rate * seconds))
    t = np.cumsum(_stratified_gaps(n, rate, rng))
    return t * (seconds / t[-1])


def onoff_arrivals(rate: float, seconds: float, rng, on_s: float,
                   off_s: float) -> np.ndarray:
    """ON/OFF arrivals: Poisson at ``rate * (on_s + off_s) / on_s`` during
    each ON phase and silence during OFF, the same mean rate as
    :func:`poisson_arrivals`, twice its peak for equal phases."""
    on_time = poisson_arrivals(rate * (on_s + off_s) / on_s,
                               seconds * on_s / (on_s + off_s), rng)
    cycle = np.floor(on_time / on_s)
    return np.minimum(cycle * (on_s + off_s) + (on_time - cycle * on_s),
                      seconds)


def arrivals(mix: dict, seconds: float, rng) -> np.ndarray:
    kind = mix["arrivals"]
    if kind == "poisson":
        return poisson_arrivals(mix["rate"], seconds, rng)
    if kind == "onoff":
        return onoff_arrivals(mix["rate"], seconds, rng, mix["on_s"],
                              mix["off_s"])
    raise ValueError(f"unknown arrivals {kind!r}")


@dataclasses.dataclass
class Request:
    vector: int               # index into the pool; -1 for a solve
    due: float                # perf_counter when it was due
    sent: float
    done: float
    y: np.ndarray | None
    error: str | None = None
    solve: object | None = None


@dataclasses.dataclass
class Window:
    start: float
    end: float
    requests: list


def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def _wait(svc, ticket, vector, due, sent, ann) -> Request:
    try:
        with _annotate("bench.result_wait", ann):
            res = svc.result(ticket, timeout=RESULT_TIMEOUT_S)
        return Request(vector, due, sent, time.perf_counter(), res.y)
    except Exception as e:  # noqa: BLE001 — a failed request is counted
        return Request(vector, due, sent, time.perf_counter(), None,
                       error=repr(e))


def _submit(svc, mid, x, owner, ann) -> int | str:
    """The request's ticket, or the reason it was refused."""
    try:
        with _annotate("bench.submit", ann):
            return svc.submit(mid, x, owner=owner)
    except Exception as e:  # noqa: BLE001 — a refused request is counted
        return repr(e)


def run_solves(svc, mid, mix, seconds, ann=False) -> Window:
    reqs = []
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        try:
            with _annotate("bench.solve", ann):
                res = svc.solve(mid, mix["solver"], timeout=RESULT_TIMEOUT_S,
                                **mix["params"])
            reqs.append(Request(-1, t0, t0, time.perf_counter(), res.y,
                                solve=res.solve))
        except Exception as e:  # noqa: BLE001 — a failed solve is counted
            reqs.append(Request(-1, t0, t0, time.perf_counter(), None,
                                error=repr(e)))
        if reqs[-1].done - start >= seconds:
            return Window(start, reqs[-1].done, reqs)


def run_closed(svc, mid, mix, seconds, pool, seed, ann=False) -> Window:
    reqs: list[Request] = []
    lock = threading.Lock()
    start = time.perf_counter()
    stop_at = start + seconds

    def client(c: int) -> None:
        rng = np.random.default_rng([seed, c])
        owner = f"client{c}"
        while time.perf_counter() < stop_at:
            j = int(rng.integers(len(pool)))
            sent = time.perf_counter()
            ticket = _submit(svc, mid, pool[j], owner, ann)
            req = (_wait(svc, ticket, j, sent, sent, ann)
                   if isinstance(ticket, int) else
                   Request(j, sent, sent, time.perf_counter(), None,
                           error=ticket))
            with lock:
                reqs.append(req)

    threads = [threading.Thread(target=client, args=(c,), name=f"client{c}")
               for c in range(mix["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return Window(start, max(r.done for r in reqs), reqs)


def run_open(svc, mid, mix, seconds, pool, seed, ann=False) -> Window:
    due = arrivals(mix, seconds, np.random.default_rng(mix["schedule_seed"]))
    vectors = np.random.default_rng([seed, 1]).integers(len(pool),
                                                        size=due.size)
    owners = mix["owners"]
    inboxes = [queue.Queue() for _ in range(owners)]
    reqs: list[Request] = []
    lock = threading.Lock()

    def collect(q: queue.Queue) -> None:
        while (item := q.get()) is not None:
            req = _wait(svc, *item, ann)
            with lock:
                reqs.append(req)

    threads = [threading.Thread(target=collect, args=(q,), name=f"owner{o}")
               for o, q in enumerate(inboxes)]
    for t in threads:
        t.start()
    start = time.perf_counter()
    for i, (d, j) in enumerate(zip(due, vectors)):
        t_due = start + float(d)
        pause = t_due - time.perf_counter()
        if pause > 0:
            with _annotate("bench.sleep", ann):
                time.sleep(pause)
        sent = time.perf_counter()
        ticket = _submit(svc, mid, pool[j], f"owner{i % owners}", ann)
        if isinstance(ticket, int):
            inboxes[i % owners].put((ticket, int(j), t_due, sent))
        else:
            with lock:
                reqs.append(Request(int(j), t_due, sent, time.perf_counter(),
                                    None, error=ticket))
    for q in inboxes:
        q.put(None)
    for t in threads:
        t.join()
    return Window(start, max(start + seconds, max(r.done for r in reqs)),
                  reqs)


def run(svc, mid, mix, seconds, pool, seed, ann=False) -> Window:
    """Drive one window of ``mix`` against ``svc``; the service must be
    started (pipelined) for the open and closed kinds."""
    kind = mix["kind"]
    if kind == "solve":
        return run_solves(svc, mid, mix, seconds, ann)
    if kind == "closed":
        return run_closed(svc, mid, mix, seconds, pool, seed, ann)
    if kind == "open":
        return run_open(svc, mid, mix, seconds, pool, seed, ann)
    raise ValueError(f"unknown traffic kind {kind!r}")
