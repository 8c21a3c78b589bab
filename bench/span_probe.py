"""The program's host spans laid over the device trace, on the chip.

    python3 bench/span_probe.py windows --workload W --seeds 1,2,3 --seconds 10
    python3 bench/span_probe.py trace --workload W --seconds 0.01 --out DIR

The cell's session serves traced windows as ``run.py --trace 1`` does,
with a ``repro.serve.spans.SpanLog`` attached to the batcher while a
traced window is open. The program's spans (``serve.pack``,
``serve.dispatch``, ``serve.resolve``) join the harness's own
(``bench.tick``, ...) in the trace reduction, so each idle gap of the
device goes to the innermost of them. Each window also keeps every
request's queue wait on the wall clock (``dispatch_ns - submit_ns``).

* ``windows`` serves, for each seed, a traced window with the log and
  one without (``--spans both``; the order alternates from seed to
  seed), or one window with (``on``) or without (``off``) it, and prints
  one JSON line per window: the end-to-end numbers, the idle gaps by
  span, each span's milliseconds per flush, the median queue wait, and
  the process's page faults and garbage collections in the window. The
  pair is what the spans cost when they are on. ``run.py`` measures a
  process's first window, so one process per seed with ``on`` or
  ``off`` reads that window's regime.
* ``trace`` records one short traced window with the log and keeps, in
  ``--out``, its ``window.xplane.pb`` and a ``window.json`` (window
  ends, the harness's and the program's spans, flush slots, counters,
  queue waits): the reducer's test fixture. It prints the program
  spans' sums by name.

Like ``run.py`` it measures only on a TPU.
"""
import argparse
import dataclasses
import gc
import json
import os
import resource
import shutil
import sys
import time
from typing import List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, session, trace_reduce  # noqa: E402
from bench.session import log, percentile  # noqa: E402

PROGRAM_SPANS = ("serve.pack", "serve.dispatch", "serve.resolve")


@dataclasses.dataclass
class SpanWindow(session.Window):
    """A window with the wall-clock views the program gives."""
    window_ns: tuple = (0, 0)
    bench_spans: Optional[List] = None    # the harness's (start, end, name)
    spans: Optional[List] = None          # the program's SpanLog rows
    queue_wait_ms: Optional[np.ndarray] = None  # per request dispatched
    host: Optional[dict] = None           # page faults, collections


class SpanSession(session.Session):
    """The cell's session, with the program's span log attached to the
    batcher in a traced window while ``record_spans`` is set."""

    record_spans = True

    def _open_window(self, trace_dir):
        from repro.serve.spans import SpanLog
        out = super()._open_window(trace_dir)
        self._w0_ns = out[0]
        self._host0 = _host_counts()
        self._log = None
        if trace_dir is not None and self.record_spans:
            self._log = SpanLog()
            self.batcher.spans = self._log
        return out

    def _close_window(self, w0, trace_dir):
        self.batcher.spans = None
        self._host = {k: v - self._host0[k]
                      for k, v in _host_counts().items()}
        counters, flushes, traced = super()._close_window(w0, trace_dir)
        self._bench_spans = None
        self._rows = self._log.rows() if self._log is not None else None
        if traced is not None:
            trace_dir, window_ns, spans = traced
            self._w1_ns = window_ns[1]
            self._bench_spans = list(spans)
            program = [(s, e, n) for s, e, n, _ in self._rows or ()]
            traced = (trace_dir, window_ns, spans + program)
        else:
            self._w1_ns = time.time_ns()
        return counters, flushes, traced

    def _window(self, seconds, t0, t1, in_win, reqs, due, sent, done_t,
                payload, counters, c0, flushes, pool, compiles, traced):
        win = super()._window(seconds, t0, t1, in_win, reqs, due, sent,
                              done_t, payload, counters, c0, flushes, pool,
                              compiles, traced)
        w0, w1 = self._w0_ns, self._w1_ns
        waits = [(r.dispatch_ns - r.submit_ns) / 1e6 for r in reqs.values()
                 if w0 <= r.dispatch_ns < w1]
        rows = None if self._rows is None else \
            [row for row in self._rows if w0 <= row[0] < w1]
        return SpanWindow(**{f.name: getattr(win, f.name)
                             for f in dataclasses.fields(win)},
                          window_ns=(w0, w1), bench_spans=self._bench_spans,
                          spans=rows, queue_wait_ms=np.asarray(waits),
                          host=self._host)


def _host_counts() -> dict:
    """The process's minor and major page faults and its garbage
    collections so far."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"minflt": ru.ru_minflt, "majflt": ru.ru_majflt,
            "gc": sum(g["collections"] for g in gc.get_stats())}


def ms_per_flush(win, name: str) -> Optional[float]:
    """Milliseconds of the program span ``name`` in the window per flush
    dispatched in it; None without the program's spans or a flush."""
    if not win.spans or not win.counters["flushes"]:
        return None
    ns = sum(e - s for s, e, n, _ in win.spans if n == name)
    return ns / 1e6 / win.counters["flushes"]


def queue_wait_ms(win) -> Optional[float]:
    """Median submit-to-dispatch wait (ms, wall clock) of the requests
    dispatched in the window; None where none was."""
    w = win.queue_wait_ms
    if w is None or not len(w):
        return None
    return float(np.median(w))


def span_ms(spans, name: str) -> float:
    """Total milliseconds of the spans named ``name`` ((start, end, name,
    ...) rows)."""
    return sum(row[1] - row[0] for row in spans if row[2] == name) / 1e6


def summary(win) -> dict:
    flushes = win.counters["flushes"]
    out = {"attempted": win.attempted, "failed": win.failed,
           "throughput": win.completed / win.seconds,
           "latency_p50": percentile(win.latency_ms, 50),
           "latency_p95": percentile(win.latency_ms, 95),
           "counters": win.counters, "compiles": win.compiles,
           "spans_on": win.spans is not None,
           "queue_wait_ms": queue_wait_ms(win), "host": win.host}
    for name in PROGRAM_SPANS:
        out[name.split(".")[1] + "_ms_per_flush"] = ms_per_flush(win, name)
    if win.trace is not None:
        t = win.trace
        out.update(busy_s=t["busy_s"], window_s=t["window_s"],
                   idle_share=100.0 * (1 - t["busy_s"] / t["window_s"]),
                   idle_gaps=trace_reduce.top(t["idle_s"], 20))
        if flushes:
            out["tick_ms_per_flush"] = span_ms(win.bench_spans,
                                               "bench.tick") / flushes
    return out


def _session(cell):
    import jax
    from repro import compile_cache
    devices = session.require_chips(cell.chips)
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = {"t": time.perf_counter()}

    def phase(name):
        now = time.perf_counter()
        log(f"{cell.name}: {name} {now - clock['t']:.3f}s")
        clock["t"] = now

    return SpanSession(cell, devices, phase), phase


def _trace_dir(name):
    return os.path.join(cells.BENCH_DIR, ".cache", "trace", name)


def windows(args):
    cell = cells.find_cell(args.workload)
    sess, phase = _session(cell)
    tmp = _trace_dir("span_probe")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        order = {"on": (True,), "off": (False,),
                 "both": (True, False) if i % 2 == 0 else (False, True)}
        for on in order[args.spans]:
            sess.record_spans = on
            win = sess.measure(seed, args.seconds, phase, trace_dir=tmp)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              **summary(win)}), flush=True)


def trace(args):
    cell = cells.find_cell(args.workload)
    sess, phase = _session(cell)
    tmp = _trace_dir("span_probe")
    win = sess.measure(args.seed, args.seconds, phase, trace_dir=tmp)
    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, "window")
    shutil.copy(session._xplane(tmp), stem + ".xplane.pb")
    meta = {"window_ns": list(win.window_ns),
            "spans": [list(s) for s in win.bench_spans],
            "program_spans": [list(r) for r in win.spans],
            "flush_slots": win.flush_slots, "counters": win.counters,
            "queue_wait_ms": win.queue_wait_ms.tolist()}
    with open(stem + ".json", "w") as f:
        json.dump(meta, f)
    sums = {name: span_ms(win.spans, name) for name in PROGRAM_SPANS}
    print(json.dumps({"program_span_ms": sums, **summary(win)}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("windows", "trace"):
        p = sub.add_parser(name)
        p.add_argument("--workload", required=True)
        p.add_argument("--seconds", type=float, required=True)
    sub.choices["windows"].add_argument("--seeds", required=True)
    sub.choices["windows"].add_argument("--spans", default="both",
                                        choices=("both", "on", "off"))
    sub.choices["trace"].add_argument("--seed", type=int, default=7)
    sub.choices["trace"].add_argument("--out", required=True)
    args = ap.parse_args(argv)
    {"windows": windows, "trace": trace}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
