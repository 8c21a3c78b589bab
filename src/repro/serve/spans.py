"""Wall-clock host spans kept in memory, on the device trace's clock.

A ``SpanLog`` keeps ``(start_ns, end_ns, name, attrs)`` rows stamped with
``time.time_ns``: the clock a JAX profiler trace's ``profile_start_time``
is on, so each span can be laid over the trace's device timeline and the
device's idle gaps attributed to what the host was doing.

The profiler's own host spans (``jax.profiler.TraceAnnotation``) need its
host tracer, and on a TPU that tracer also records the runtime's events,
about 34,000 a DarkNet-19 flush, which cut the served rate five-fold. A
``SpanLog`` reads the clock twice a span and appends one tuple.

A program that records spans holds an optional ``SpanLog`` and checks it
for ``None`` at each span site, so with no log attached no clock is read
and no attributes are built. The log is never fed through a program's
event stream (``on_event``), whose payloads replay bit for bit.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

Row = Tuple[int, int, str, Dict]


class SpanLog:
    """Host spans in memory: ``record(start_ns, name, **attrs)`` keeps a
    span that ends now. The log has no bound: attach it for a measured
    window and detach it after."""
    __slots__ = ("_rows",)

    def __init__(self):
        self._rows: List[Row] = []

    def record(self, start_ns: int, name: str, **attrs):
        self._rows.append((start_ns, time.time_ns(), name, attrs))

    def rows(self) -> List[Row]:
        return list(self._rows)
