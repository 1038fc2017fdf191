"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

``reduce_trace`` reads one trace with ``jax.profiler.ProfileData`` and
returns, for the window that a host annotation marks:

* ``busy_s``: the union of the intervals in which an operation ran on
  each device ("XLA Ops" line of each ``/device:TPU:n`` plane), averaged
  over the devices;
* ``window_s``: the window's length;
* ``ops``: every device operation in the window as (name, start_ns,
  duration_ns, full HLO text), for per-kernel readers;
* ``device_ops``: the ten operations that took most device time,
  counting only operations that contain no other (a ``while`` loop's
  event spans the operations of its body);
* ``idle_gaps``: the device's idle time in the window, grouped by what
  the host was doing: each gap goes to the innermost host event that
  covers its middle on the thread that holds the window annotation,
  else on any thread, else to ``host:untraced``; the ten largest
  groups.

Device and host timestamps come from one clock in the trace; they may
sit up to about a millisecond apart, which moves nothing measured over
a window of seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from collections import defaultdict
from typing import List, Optional, Tuple

__all__ = ["TraceSummary", "find_xplane", "reduce_trace", "union_ns",
           "op_name", "kernel_ops"]

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_TID = re.compile(r"/\d+$")
UNTRACED = "host:untraced"


@dataclasses.dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    n_devices: int
    ops: List[Tuple[str, float, float, str]]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def find_xplane(directory: str) -> str:
    """The one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory},"
                                f" found {len(paths)}")
    return paths[0]


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def union_ns(intervals, lo: float, hi: float) -> Tuple[float, list]:
    """(covered length, gaps) of ``intervals`` [(start, end)] clipped to
    [lo, hi]; gaps are the uncovered (start, end) pieces in order."""
    covered, gaps, cursor = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s or e <= cursor:
            continue
        if s > cursor:
            gaps.append((cursor, s))
            cursor = s
        covered += e - cursor
        cursor = e
    if cursor < hi:
        gaps.append((cursor, hi))
    return covered, gaps


def _innermost(points, events):
    """For each of the sorted ``points``, the name of the shortest event
    [(start, end, name)] that covers it, or None."""
    events = sorted(events)
    active = []                     # heap of (duration, end, name)
    names, i = [], 0
    for p in points:
        while i < len(events) and events[i][0] <= p:
            s, e, name = events[i]
            heapq.heappush(active, (e - s, e, name))
            i += 1
        while active and active[0][1] < p:
            heapq.heappop(active)
        names.append(active[0][2] if active else None)
    return names


def _attribute(gaps, host_events, main_events=()):
    """{name: idle ns}: each gap to the innermost event of
    ``main_events`` covering its middle, else of ``host_events``.
    Events are (start, end, name)."""
    gaps = sorted(gaps)
    mids = [0.5 * (g0 + g1) for g0, g1 in gaps]
    out = defaultdict(float)
    for (g0, g1), main, any_ in zip(gaps, _innermost(mids, main_events),
                                    _innermost(mids, host_events)):
        out[main or any_ or UNTRACED] += g1 - g0
    return out


def _leaves(events):
    """The events [(start, duration, text)] that contain no other."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    keep = [True] * len(events)
    for i in range(len(events) - 1):
        s, d, _ = events[i]
        t, u, _ = events[i + 1]
        if t < s + d and t + u <= s + d:
            keep[i] = False
    return [e for e, k in zip(events, keep) if k]


def _top(totals: dict, n: int = 10):
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce_trace(path: str, window: str = "bench.window",
                 window_index: int = -1) -> TraceSummary:
    """Reduce the trace at ``path`` over the host annotation ``window``
    (its ``window_index``-th occurrence, the last by default)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_lines, host_lines, marks = [], [], []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_lines.append([(e.start_ns, e.duration_ns, e.name)
                                         for e in line.events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                thread = _TID.sub("", line.name)
                events, has_window = [], False
                for e in line.events:
                    if e.name == window:
                        marks.append((e.start_ns, e.start_ns + e.duration_ns))
                        has_window = True
                    else:
                        events.append((e.start_ns, e.start_ns + e.duration_ns,
                                       f"{thread}:{e.name}"))
                host_lines.append((has_window, events))
    if not marks:
        raise ValueError(f"no host annotation {window!r} in {path}")
    if not device_lines:
        raise ValueError(f"no device operations in {path}")
    lo, hi = sorted(marks)[window_index]
    host_events = [e for _, events in host_lines for e in events]
    main_events = [e for main, events in host_lines if main for e in events]
    busy, ops = 0.0, []
    op_time = defaultdict(float)
    idle = defaultdict(float)
    for events in device_lines:
        covered, gaps = union_ns(((s, s + d) for s, d, _ in events), lo, hi)
        busy += covered
        for gap_name, ns in _attribute(gaps, host_events,
                                       main_events).items():
            idle[gap_name] += ns / len(device_lines)
        inside = [(s, d, t) for s, d, t in events if s >= lo and s + d <= hi]
        ops += [(op_name(t), s, d, t) for s, d, t in inside]
        for s, d, text in _leaves(inside):
            op_time[op_name(text)] += d / len(device_lines)
    return TraceSummary(busy_s=busy / len(device_lines) / 1e9,
                        window_s=(hi - lo) / 1e9,
                        n_devices=len(device_lines), ops=ops,
                        device_ops=_top(op_time), idle_gaps=_top(idle))


def kernel_ops(summary: Optional[TraceSummary], pattern: str):
    """The window's device operations of a Pallas kernel whose name
    contains ``pattern``: [(name, duration_ns, HLO text)]."""
    if summary is None:
        return []
    return [(n, d, t) for n, _, d, t in summary.ops
            if pattern in n and "tpu_custom_call" in t]
