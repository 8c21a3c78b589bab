"""Reduces a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* The window and the harness's host spans (``bench.tick``,
  ``bench.submit``, ``bench.client``, ``bench.wait``) come from the
  harness, stamped with the host's wall clock (``time.time_ns``); the
  trace's times count from its ``profile_start_time``, on the same clock.
  The harness traces with the host tracer off: on a TPU the runtime's own
  host events (one per tile of every host-to-device transpose) slowed the
  served rate five-fold.
* A device is a ``/device:TPU:<n>`` plane; its operations are the events
  of its ``XLA Ops`` line. Busy time is the union of their intervals
  inside the window. A device plane without that line, or a window in
  which no operation ran, is an error: never an idle device.
* An operation's name is its HLO instruction's name without the numeric
  or clone suffix: a TPU names its events by the instruction's whole text
  (``%fq_conv2d.3 = s8[8,56,512]{...} custom-call(...)``), which becomes
  ``fq_conv2d``, so every call of a kernel sums under one name.
* Each idle gap inside the window is attributed to the host spans by
  overlap, to the innermost; idle time under none of them is ``other``.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
START_STAT = "profile_start_time"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"(\.\d+|\.clone)+$")


def op_name(name: str) -> str:
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _attribute(gaps, spans, into: Dict[str, float]):
    """Add each gap's overlap with host spans to ``into`` (ns), by span
    name; the rest of the gap goes to ``other``. Spans may nest: a moment
    covered by several counts once, for the innermost (latest-started)."""
    points = []
    for s, e, name in spans:
        points.append((s, 1, name))
        points.append((e, -1, name))
    points.sort(key=lambda p: (p[0], -p[1]))
    active: List[str] = []
    pi = 0
    for g0, g1 in gaps:
        # advance the span stack to g0
        while pi < len(points) and points[pi][0] <= g0:
            _step(active, points[pi])
            pi += 1
        t = g0
        while t < g1:
            nxt = points[pi][0] if pi < len(points) else g1
            end = min(nxt, g1)
            if end > t:
                into[active[-1] if active else "other"] += end - t
                t = end
            if end == nxt and pi < len(points):
                _step(active, points[pi])
                pi += 1


def _step(active, point):
    _, kind, name = point
    if kind == 1:
        active.append(name)
    elif name in active:
        active.reverse()
        active.remove(name)
        active.reverse()


def reduce(path: str, window: Tuple[int, int],
           spans: List[Tuple[int, int, str]]) -> Dict:
    """The trace at ``path`` (an ``.xplane.pb``), reduced."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), window, spans)


def profile_start(pd) -> int:
    """The wall-clock ns at which the trace's times start."""
    for plane in pd.planes:
        for name, value in plane.stats:
            if name == START_STAT:
                return int(value)
    raise ValueError(f"the trace has no {START_STAT!r} stat")


def reduce_profile(pd, window: Tuple[int, int],
                   spans: List[Tuple[int, int, str]]) -> Dict:
    """A ``jax.profiler.ProfileData``, reduced over ``window`` (wall-clock
    ns): per-device busy time, op seconds, and idle seconds by host span
    (``spans``: wall-clock ns and name). Seconds of ops and idle gaps are
    summed over the devices; ``busy_s`` is the mean over devices."""
    base = profile_start(pd)
    w0, w1 = window[0] - base, window[1] - base
    spans = sorted((s - base, e - base, name) for s, e, name in spans)
    devices = {}
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                raise ValueError(f"device plane {plane.name} has no "
                                 f"{OPS_LINE!r} line; lines: {sorted(lines)}")
            devices[plane.name] = [(int(ev.start_ns), int(ev.end_ns), ev.name)
                                   for ev in lines[OPS_LINE].events]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    op_ns: Dict[str, float] = collections.defaultdict(float)
    idle_ns: Dict[str, float] = collections.defaultdict(float)
    busy = {}
    for dev, ops in devices.items():
        clipped = []
        for s, e, name in ops:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                clipped.append((s, e))
                op_ns[op_name(name)] += e - s
        merged = _union(clipped)
        busy[dev] = sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        _attribute(gaps, spans, idle_ns)
    if not any(busy.values()):
        raise ValueError(f"no device operation ran inside the window on "
                         f"{sorted(devices)}")
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_by_device": busy,
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "idle_s": {k: v / 1e9 for k, v in idle_ns.items()},
    }


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
