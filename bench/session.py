"""One cell's set-up, measured window and output check.

The window drives the system's normal serving path: ``CNNBatcher.submit``
and ``tick`` over the configuration's ``int_serve_fn`` step, with its shape
ladder, batch buckets and dispatch-ahead window. Completions are taken from
the batcher's own ``resolve`` events, stamped with the host clock.

* Closed loop: each client sends its next request as soon as the last is
  answered. The window opens after ``ramp_requests`` answers; a request is
  due when it is submitted.
* Open loop: requests are due at ``t0 + offset`` from ``traffic_gen``; a
  request is submitted at the first loop turn at or after its due time,
  and how late that was is reported. With nothing queued or in flight the
  loop only watches the clock (one ``bench.wait`` span per idle stretch).

In a traced window the harness keeps its own host spans (``bench.tick``,
``bench.submit``, ``bench.client``, ``bench.wait``) and the window's ends on
the wall clock, and traces the device with the host tracer off
(``trace_reduce`` says why).

After the window closes no new request is sent, and the loop keeps ticking
until every request due in the window is answered, or ``GRACE_S`` has
passed. Latency runs from due time to answer; an unanswered or shed
request is failed, with infinite latency.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import refops, trace_reduce, traffic_gen

GRACE_S = 60.0
COUNTERS = ("flushes", "served", "padded_rows", "window_waits")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"the benchmark measures on a TPU; JAX found "
                     f"{devs[0].platform if devs else 'no'} devices")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chips; JAX found {len(devs)}")
    return devs[:n]


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Window:
    """What one measured window produced."""
    seconds: float
    t0: float
    t1: float
    completed: int                 # answered inside [t0, t1)
    attempted: int                 # due inside [t0, t1)
    failed: int                    # due in the window, shed or unanswered
    latency_ms: np.ndarray         # per due request; inf where failed
    lateness_ms: np.ndarray        # submit - due, per due request
    counters: Dict[str, int]       # batcher counter deltas over the window
    flush_slots: List[int]         # padded batch of each flush dispatched
    answers: List                  # (payload index, logits) per answered
    pool: List[np.ndarray]
    compiles: int                  # compilations inside the window
    trace: Optional[Dict] = None


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile: a missing (inf) request is counted as the
    slowest, and never averaged away."""
    if len(values) == 0:
        return math.inf
    s = np.sort(values)
    return float(s[max(0, math.ceil(q / 100 * len(s)) - 1)])


class Session:
    """The system under test, built once and warmed up, for one cell."""

    def __init__(self, cell, devices, phase):
        import jax
        from repro.core import fq_layers
        from repro.serve.cnn_batching import CNNBatcher
        self.cell, self.devices = cell, devices
        spec, model = cell.spec, cell.model
        if fq_layers.EDGE_PRECISION != spec["edge_precision"]:
            raise RuntimeError(
                f"the program computes its float edges at "
                f"{fq_layers.EDGE_PRECISION!r}; the configuration states "
                f"{spec['edge_precision']!r}")
        self.params, self.state = model.checkpoint(spec)
        phase("checkpoint")
        ladder, fns = model.program(spec, self.params, self.state, devices)
        phase("convert")
        b = spec["batcher"]
        self.max_batch = b["max_batch"]
        kw = dict(ladder=ladder, max_batch=b["max_batch"],
                  max_wait_ticks=b["max_wait_ticks"],
                  dispatch_ahead=b["dispatch_ahead"],
                  max_inflight=b["max_inflight"], on_event=self._on_event)
        if len(fns) > 1:
            kw.update(n_replicas=len(fns), replica_apply_fns=fns,
                      replica_devices=devices)
        self.batcher = CNNBatcher(fns[0], **kw)
        self._resolved: List = []
        self._flushes: List[int] = []
        self._rid = 0
        self._compiles = 0
        self._spans: Optional[List] = None  # host spans of a traced window
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        self._warm(phase)

    def _on_event(self, etype, kw):
        if etype == "resolve":
            t = time.perf_counter()
            self._resolved.extend((r, t) for r in kw["reqs"])
        elif etype == "flush":
            self._flushes.append(kw["slots"])

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compiles += 1

    def _warm(self, phase):
        """Compile (or load from the cache) every batch bucket of the rung
        on every lane, through the batcher: lanes take flushes in turn."""
        from repro.serve.cnn_batching import CNNRequest
        shape = self.cell.model.rung_shape(self.cell.spec)
        slots = 1
        while True:
            for _ in range(len(self.devices)):
                reqs = [CNNRequest(rid=-1, x=np.zeros(shape, np.float32))
                        for _ in range(slots)]
                self.batcher.submit(reqs)
                while not all(r.done for r in reqs):
                    self.batcher.tick()
                bad = [r.error for r in reqs if r.error is not None]
                if bad:
                    raise RuntimeError(f"warm-up flush failed: {bad[0]}")
            phase(f"warm b{slots}")
            if slots >= self.max_batch:
                break
            slots = min(2 * slots, self.max_batch)
        self._resolved.clear()
        self._flushes.clear()

    def _span(self, name):
        return _Span(self._spans, name)

    def _counters(self):
        st = self.batcher.stats
        return {k: st[k] for k in COUNTERS}

    def measure(self, seed: int, seconds: float, phase,
                trace_dir: Optional[str] = None) -> Window:
        tr = self.cell.traffic
        spec, model = self.cell.spec, self.cell.model
        pool = traffic_gen.payload_pool(
            tr, lambda dims: model.payload_shape(spec, dims), seed)
        phase("payloads")
        loop = self._closed if tr["loop"] == "closed" else self._open
        return loop(tr, pool, seed, seconds, trace_dir)

    # -- the measured loops --------------------------------------------------

    def _open_window(self, trace_dir):
        import jax
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir, profiler_options=_trace_opts())
            self._spans = []
        self._flushes.clear()
        return time.time_ns(), self._counters(), self._compiles

    def _close_window(self, w0, trace_dir):
        """The window's counters and flushes, and with a trace the trace's
        location, the window's ends and the host spans (wall-clock ns)."""
        import jax
        traced = None
        if trace_dir is not None:
            traced = (trace_dir, (w0, time.time_ns()), self._spans)
            self._spans = None
        counters = self._counters()
        flushes = list(self._flushes)
        if trace_dir is not None:
            jax.profiler.stop_trace()
        return counters, flushes, traced

    def _closed(self, tr, pool, seed, seconds, trace_dir):
        from repro.serve.cnn_batching import CNNRequest
        ta = self._span
        b = self.batcher
        due: Dict[int, float] = {}
        payload: Dict[int, int] = {}
        reqs: Dict[int, object] = {}
        done_t: Dict[int, float] = {}
        state = {"next": 0}

        def send(n, now):
            new = []
            for _ in range(n):
                i = state["next"] % len(pool)
                r = CNNRequest(rid=self._rid, x=pool[i])
                payload[r.rid] = i
                due[r.rid], reqs[r.rid] = now, r
                state["next"] += 1
                self._rid += 1
                new.append(r)
            with ta("bench.submit"):
                b.submit(new)

        send(tr["clients"], time.perf_counter())
        ramp = tr["ramp_requests"]
        t0 = t1 = None
        while True:
            with ta("bench.tick"):
                b.tick()
            with ta("bench.client"):
                now = time.perf_counter()
                answered = self._resolved
                self._resolved = []
                for r, t in answered:
                    done_t[r.rid] = t
                if t0 is None:
                    ramp -= len(answered)
                    if ramp <= 0:
                        w0, c0, k0 = self._open_window(trace_dir)
                        t0 = time.perf_counter()
                        t1 = t0 + seconds
                        now = t0
                if t1 is not None and now >= t1:
                    break
                if answered:
                    send(len(answered), now)
        counters, flushes, traced = self._close_window(w0, trace_dir)
        compiles = self._compiles - k0
        in_win = [rid for rid, d in due.items() if t0 <= d < t1]
        self._finish(lambda: all(reqs[i].done for i in in_win), t1)
        for r, t in self._resolved:
            done_t[r.rid] = t
        self._resolved = []
        return self._window(seconds, t0, t1, in_win, reqs, due, due, done_t,
                            payload, counters, c0, flushes, pool, compiles,
                            traced)

    def _open(self, tr, pool, seed, seconds, trace_dir):
        from repro.serve.cnn_batching import CNNRequest
        ta = self._span
        b = self.batcher
        offsets = traffic_gen.open_schedule(tr, seconds, seed)
        n = len(offsets)
        reqs = {self._rid + i: CNNRequest(rid=self._rid + i,
                                          x=pool[i % len(pool)])
                for i in range(n)}
        rids = list(reqs)
        payload = {rid: i % len(pool) for i, rid in enumerate(rids)}
        self._rid += n
        done_t: Dict[int, float] = {}
        sent: Dict[int, float] = {}
        w0, c0, k0 = self._open_window(trace_dir)
        t0 = time.perf_counter()
        t1 = t0 + seconds
        due_at = t0 + offsets
        due = dict(zip(rids, due_at))
        k, wait = 0, None
        while True:
            now = time.perf_counter()
            busy = b.outstanding() > 0
            if k < n and due_at[k] <= now:
                j = int(np.searchsorted(due_at, now, side="right"))
                for rid in rids[k:j]:
                    sent[rid] = now
                wait = _leave(wait)
                with ta("bench.submit"):
                    b.submit([reqs[rid] for rid in rids[k:j]])
                k, busy = j, True
            elif k == n and now >= t1:
                break
            if busy:
                wait = _leave(wait)
                with ta("bench.tick"):
                    b.tick()
                for r, t in self._resolved:
                    done_t[r.rid] = t
                self._resolved = []
            elif wait is None:  # nothing to do until the next arrival
                wait = ta("bench.wait")
                wait.__enter__()
        _leave(wait)
        counters, flushes, traced = self._close_window(w0, trace_dir)
        compiles = self._compiles - k0
        self._finish(lambda: all(r.done for r in reqs.values()), t1)
        for r, t in self._resolved:
            done_t[r.rid] = t
        self._resolved = []
        return self._window(seconds, t0, t1, rids, reqs, due, sent, done_t,
                            payload, counters, c0, flushes, pool, compiles,
                            traced)

    def _finish(self, all_done, t1):
        """Tick until every due request is answered or the grace ends."""
        while not all_done() and time.perf_counter() < t1 + GRACE_S:
            self.batcher.tick()

    def _window(self, seconds, t0, t1, in_win, reqs, due, sent, done_t,
                payload, counters, c0, flushes, pool, compiles, traced):
        lat, late, answers, failed = [], [], [], 0
        for rid in in_win:
            r = reqs[rid]
            late.append((sent[rid] - due[rid]) * 1e3)
            if r.done and r.error is None and rid in done_t:
                lat.append((done_t[rid] - due[rid]) * 1e3)
                answers.append((payload[rid], r.out))
            else:
                lat.append(math.inf)
                failed += 1
        completed = sum(1 for t in done_t.values() if t0 <= t < t1)
        trace = None
        if traced is not None:
            trace_dir, window_ns, spans = traced
            trace = trace_reduce.reduce(_xplane(trace_dir), window_ns, spans)
        return Window(
            seconds=seconds, t0=t0, t1=t1, completed=completed,
            attempted=len(in_win), failed=failed,
            latency_ms=np.asarray(lat), lateness_ms=np.asarray(late),
            counters={k: counters[k] - c0[k] for k in COUNTERS},
            flush_slots=flushes, answers=answers, pool=pool,
            compiles=compiles, trace=trace)

    # -- after the window ----------------------------------------------------

    def memory_peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))

    def release(self):
        """Free the program's state before the reference runs."""
        import jax
        self.batcher = None
        gc.collect()
        jax.clear_caches()


class _Span:
    """A host span of the harness: kept, with its wall-clock ends, in
    ``rows`` while a traced window is open (``rows`` is a list), else
    nothing."""
    __slots__ = ("rows", "name", "t")

    def __init__(self, rows, name):
        self.rows, self.name = rows, name

    def __enter__(self):
        self.t = time.time_ns()
        return self

    def __exit__(self, *exc):
        if self.rows is not None:
            self.rows.append((self.t, time.time_ns(), self.name))


def _leave(span):
    if span is not None:
        span.__exit__(None, None, None)


def _trace_opts():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    return opts


def _xplane(trace_dir: str) -> str:
    found = []
    for dirpath, _, files in os.walk(trace_dir):
        found += [os.path.join(dirpath, f) for f in files
                  if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def check(cell, params, state, win: Window):
    """Every answer due in the window against the plain reference's logits
    for its payload, its float edges at the configuration's
    ``edge_precision``. Returns (correct, checks), each check a value with
    its limit."""
    ref = cell.model.Reference(cell.spec, params, state,
                               cell.spec["edge_precision"]).logits(win.pool)
    gap = gap_of(win.answers, ref)
    limit = cell.spec["limits"]["logit_gap"]
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "failed": {"value": win.failed, "limit": 0}}
    correct = (win.attempted > 0 and win.failed == 0
               and math.isfinite(gap) and gap <= limit)
    return correct, checks


def gap_of(answers, ref: np.ndarray, block: int = 4096) -> float:
    """The widest logit gap (``refops.logit_gap``) over the answers; inf
    where any answer is not finite."""
    worst = 0.0
    for i in range(0, len(answers), block):
        part = answers[i:i + block]
        idx = np.array([p for p, _ in part])
        out = np.stack([o for _, o in part])
        g = refops.logit_gap(out, ref[idx])
        if not np.all(np.isfinite(g)):
            return math.inf
        worst = max(worst, float(g.max()))
    return worst
