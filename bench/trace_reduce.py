"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The traced stretch is the host annotation ``bench.window``.  Inside it:

* busy time: the union of the intervals in which a program or an op ran
  on a device (the ``XLA Modules`` and ``XLA Ops`` lines of its
  ``/device:TPU:n`` plane), for each of the cell's devices, and its mean
  over them; a plane of a device outside the cell is left out;
* the device ops that took most self time (nested ops subtracted) on the
  cell's busiest device, the one that sets the pace, named
  ``<program>/<op>`` with the hashes and HLO operands stripped;
* the idle gaps of that device, each named by what the host was
  doing meanwhile: a harness annotation (``bench.*``), a program span
  mirrored onto the trace's clock (``repro.*``, see
  :func:`clock_offset_ns`), or ``xla.compile`` while XLA compiled or
  loaded a program.  The most specific activity that covers at least
  half of the gap names it, else the one that overlaps it most.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
BUSY_LINES = ("XLA Modules", "XLA Ops")
WINDOW = "bench.window"
SYNC = "bench.sync"
COMPILE_MARKS = ("XLA::TPU lowering", "LoadProgramCacheMiss",
                 "backend_compile")
TOP = 10


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _events(line):
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def host_events(profile) -> list[tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of every host event."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(_events(line))
    return out


def find_span(events, name: str) -> tuple[int, int]:
    """The first host event called ``name``."""
    for ev_name, t0, t1 in events:
        if ev_name == name:
            return t0, t1
    raise ValueError(f"the trace has no host event {name!r}")


def clock_offset_ns(events, perf_ns_at_sync: int) -> int:
    """Add this to a ``time.perf_counter_ns()`` reading to put it on the
    trace's clock; ``perf_ns_at_sync`` was read just before entering the
    ``bench.sync`` annotation."""
    return find_span(events, SYNC)[0] - perf_ns_at_sync


def union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for t0, t1 in sorted(intervals):
        if merged and t0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t1)
        else:
            merged.append([t0, t1])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _op_name(name: str) -> str:
    return name.split(" = ")[0].lstrip("%").strip()


def _module_name(name: str) -> str:
    return name.split("(")[0]


def _device_lines(plane) -> dict[str, list]:
    return {line.name: _events(line) for line in plane.lines
            if line.name in BUSY_LINES}


def device_busy(lines: dict, lo: int, hi: int) -> list[tuple[int, int]]:
    spans = [(t0, t1) for evs in lines.values() for _, t0, t1 in evs]
    return clip(union(spans), lo, hi)


def self_times(events) -> list[tuple[str, int, int, int]]:
    """``(name, start, end, self_ns)``: an op's time less the ops nested
    in it on the same line (a ``while`` holds its body's ops)."""
    out: list[list] = []
    stack: list[int] = []
    for name, t0, t1 in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][2] <= t0:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= t1 - t0
        out.append([name, t0, t1, t1 - t0])
        stack.append(len(out) - 1)
    return [tuple(e) for e in out]


def top_ops(lines: dict, lo: int, hi: int) -> list[list]:
    """The ops with the most self time inside ``[lo, hi)``."""
    modules = sorted(lines.get("XLA Modules", []), key=lambda e: e[1])
    starts = [m[1] for m in modules]
    total: dict[str, float] = defaultdict(float)
    for name, t0, t1, own in self_times(lines.get("XLA Ops", [])):
        inside = min(t1, hi) - max(t0, lo)
        if inside <= 0 or own <= 0:
            continue
        i = bisect.bisect_right(starts, t0) - 1
        module = (_module_name(modules[i][0])
                  if i >= 0 and modules[i][2] >= t0 else "?")
        total[f"{module}/{_op_name(name)}"] += own * inside / (t1 - t0) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            ][:TOP]


def _activities(events, extra) -> list[tuple[str, int, int]]:
    acts = [(name, t0, t1) for name, t0, t1 in events
            if name.startswith("bench.") and name not in (WINDOW, SYNC)]
    acts += [("xla.compile", t0, t1) for name, t0, t1 in events
             if any(m in name for m in COMPILE_MARKS)]
    return acts + list(extra)


def label_gap(gap: tuple[int, int], activities) -> str:
    g0, g1 = gap
    covering, best, best_overlap = [], "idle host", 0
    for name, t0, t1 in activities:
        overlap = min(g1, t1) - max(g0, t0)
        if overlap <= 0:
            continue
        if 2 * overlap >= g1 - g0:
            covering.append((t1 - t0, name))
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return min(covering)[1] if covering else best


def device_planes(profile, device_ids=None) -> dict:
    """The ``/device:TPU:n`` planes by ``n``, in ascending order; only
    those of ``device_ids`` where it is given.  Raises ``ValueError`` for
    an id with no plane, or a trace with none: no device number can come
    from it."""
    planes = {int(m.group(1)): p for p in profile.planes
              if (m := DEVICE_PLANE.match(p.name))}
    ids = sorted(planes if device_ids is None else device_ids)
    if not ids or any(i not in planes for i in ids):
        raise ValueError(f"the trace has TPU planes {sorted(planes)}; "
                         f"the cell's devices are {ids}")
    return {i: planes[i] for i in ids}


def reduce_profile(profile, extra_host=(), device_ids=None) -> dict:
    """Busy and window seconds, top device ops and labelled idle gaps of
    the ``bench.window`` stretch.  ``extra_host`` adds host activities
    ``(name, start_ns, end_ns)`` already on the trace's clock.

    ``device_ids`` are the cell's devices (``jax.Device.id``, the ``n``
    of ``/device:TPU:n``); without them every TPU plane counts.  Busy time
    is given for each (``busy_s_by_device``, by ascending id) and as their
    mean (``busy_s``); the top ops and the idle gaps are those of the
    busiest, the first of equals."""
    events = host_events(profile)
    lo, hi = find_span(events, WINDOW)
    planes = device_planes(profile, device_ids)
    lines = [_device_lines(p) for p in planes.values()]
    busy = [device_busy(ln, lo, hi) for ln in lines]
    busy_by_device = [sum(b - a for a, b in bs) / 1e9 for bs in busy]
    pace = busy_by_device.index(max(busy_by_device))
    edges = [lo] + [t for a, b in busy[pace] for t in (a, b)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    acts = _activities(events, extra_host)
    labelled = [(label_gap(g, acts), (g[1] - g[0]) / 1e9) for g in gaps]
    by_label: dict[str, float] = defaultdict(float)
    for name, s in labelled:
        by_label[name] += s
    return {
        "busy_s": sum(busy_by_device) / len(planes),
        "busy_s_by_device": busy_by_device,
        "device_ids": list(planes),
        "window_s": (hi - lo) / 1e9,
        "devices": len(planes),
        "device_ops": top_ops(lines[pace], lo, hi),
        "idle_gaps": [[n, s] for n, s in
                      sorted(labelled, key=lambda g: -g[1])[:TOP]],
        "idle_by_activity": dict(sorted(by_label.items(),
                                        key=lambda kv: -kv[1])),
    }


def roofline_pct(run) -> float | None:
    """Share of the HBM roofline: the window's counted bytes
    (``bench/work.py``) at the peak bandwidth of the cell's ``run.chips``
    chips (``bench/peaks.json``) over the devices' mean busy time in the
    traced window.  HBM bounds SpMV: 2 flops per 8 or more bytes lies far
    below the chip's ridge."""
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    nbytes, _ = run.work()
    if nbytes == 0:
        return None
    peak = run.peaks["hbm_bytes_per_s"] * run.chips
    return 100.0 * nbytes / peak / run.trace["busy_s"]


def idle_pct(run) -> float | None:
    """Share of the traced window in which no program ran on the cell's
    devices, on average over them."""
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
