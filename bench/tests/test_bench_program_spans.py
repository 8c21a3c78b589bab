"""The program's host spans in the trace reduction (``span_probe``): idle
gaps go to the innermost of the harness's and the program's spans, on
hand-made intervals and on a window recorded on a TPU v5e with the
batcher's ``SpanLog`` attached (``fixtures/darknet19_v5e_spans_window.*``);
the span readings per flush and the wall-clock queue wait; and the span
session's traced window on the CPU with the profiler faked."""
import json
import os
import types

import jax
import numpy as np
import pytest

from bench import session, span_probe
from bench import trace_reduce as T
from bench.tests import helpers
from bench.tests.test_bench_trace_reduce import BASE, TRACE, WINDOW

US = 1000  # ns
SEED = 2 ** 31 + 7
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "darknet19_v5e_spans_window")


def _pd(text=TRACE):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def test_idle_gaps_go_to_program_spans_nested_in_tick():
    # ops run 2-4 and 5-6 us of the 1-11 us window: idle 1-2, 4-5, 6-11
    spans = [(BASE + s * US // 10, BASE + e * US // 10, name)
             for s, e, name in [(15, 105, "bench.tick"),
                                (16, 19, "serve.pack"),
                                (19, 42, "serve.dispatch"),
                                (45, 65, "serve.resolve")]]
    r = T.reduce_profile(_pd(), WINDOW, spans)
    assert r["idle_s"] == pytest.approx({
        "other": 1.0e-6, "serve.pack": 0.3e-6, "serve.dispatch": 0.3e-6,
        "serve.resolve": 1.0e-6, "bench.tick": 4.4e-6})
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def _fixture():
    with open(FIXTURE + ".json") as f:
        meta = json.load(f)
    program = [tuple(r) for r in meta["program_spans"]]
    spans = [tuple(s) for s in meta["spans"]] + [r[:3] for r in program]
    r = T.reduce(FIXTURE + ".xplane.pb", tuple(meta["window_ns"]), spans)
    win = types.SimpleNamespace(counters=meta["counters"], spans=program,
                                queue_wait_ms=np.array(meta["queue_wait_ms"]))
    return meta, win, r


def test_chip_window_idle_by_program_span():
    """Four flushes of 8 DarkNet-19 requests, traced on a TPU v5e: the
    device's idle time goes to the batcher's spans, little to the
    harness's ``bench.tick`` around them."""
    meta, win, r = _fixture()
    assert r["window_s"] == pytest.approx(0.01585181)
    assert r["busy_s"] == pytest.approx(0.006678606)
    idle = r["idle_s"]
    assert idle == pytest.approx({
        "serve.resolve": 0.003440586, "serve.pack": 0.002648658,
        "serve.dispatch": 0.00094007, "other": 0.00126187,
        "bench.submit": 0.0004994, "bench.tick": 0.00037311,
        "bench.client": 9.51e-06})
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # every flush dispatched in the window packs, then dispatches, once
    packed = [a["flush"] for s, e, n, a in win.spans if n == "serve.pack"]
    sent = [a["flush"] for s, e, n, a in win.spans if n == "serve.dispatch"]
    assert packed == sent == [38, 39, 40, 41]
    assert len(packed) == meta["counters"]["flushes"]
    assert all(a["bytes"] == 8 * 224 * 224 * 3 * 4
               for s, e, n, a in win.spans if n == "serve.pack")


def test_readings_on_the_chip_window():
    meta, win, r = _fixture()
    assert span_probe.ms_per_flush(win, "serve.pack") == \
        pytest.approx(0.8441)
    assert span_probe.ms_per_flush(win, "serve.dispatch") == \
        pytest.approx(0.29892)
    assert span_probe.ms_per_flush(win, "serve.resolve") == \
        pytest.approx(2.084205)
    assert span_probe.queue_wait_ms(win) == pytest.approx(4.793705)
    assert len(win.queue_wait_ms) == meta["counters"]["served"]


def _row(s, e, name, flush):
    return (s, e, name, {"flush": flush})


def test_readings_per_flush_and_queue_wait():
    win = types.SimpleNamespace(
        counters={"flushes": 2},
        spans=[_row(0, 1_000_000, "serve.pack", 0),
               _row(1_000_000, 4_000_000, "serve.dispatch", 0),
               _row(5_000_000, 5_500_000, "serve.pack", 1),
               _row(5_500_000, 6_500_000, "serve.dispatch", 1),
               _row(7_000_000, 9_000_000, "serve.resolve", 0)],
        queue_wait_ms=np.array([3.0, 1.0, 2.0, 10.0, 4.0]))
    assert span_probe.ms_per_flush(win, "serve.pack") == pytest.approx(0.75)
    assert span_probe.ms_per_flush(win, "serve.dispatch") == \
        pytest.approx(2.0)
    assert span_probe.ms_per_flush(win, "serve.resolve") == \
        pytest.approx(1.0)
    assert span_probe.queue_wait_ms(win) == 3.0
    assert span_probe.span_ms(win.spans, "serve.pack") == pytest.approx(1.5)
    # nothing to read: no spans attached, no flush, no request dispatched
    for absent in (dict(spans=None), dict(spans=[]),
                   dict(counters={"flushes": 0})):
        w = types.SimpleNamespace(**{**vars(win), **absent})
        assert span_probe.ms_per_flush(w, "serve.pack") is None
    for waits in (None, np.array([])):
        w = types.SimpleNamespace(**{**vars(win), "queue_wait_ms": waits})
        assert span_probe.queue_wait_ms(w) is None


@pytest.fixture
def fake_profiler(monkeypatch):
    """Traced windows on the CPU: the profiler does nothing and the
    reduction records the spans it is given."""
    got = []

    def reduce(path, window_ns, spans):
        got.append((window_ns, list(spans)))
        s = (window_ns[1] - window_ns[0]) / 1e9
        return {"window_s": s, "busy_s": s / 2,
                "busy_s_by_device": {"/device:TPU:0": s / 2},
                "op_s": {}, "idle_s": {"bench.tick": s / 2}}

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(session, "_xplane", lambda d: d)
    monkeypatch.setattr(session.trace_reduce, "reduce", reduce)
    return got


def _inside(row, spans, name):
    return any(s <= row[0] and row[1] <= e for s, e, n in spans if n == name)


@pytest.mark.parametrize("model,mix", [("darknet", helpers.TINY_CLOSED),
                                       ("kws", helpers.TINY_OPEN)])
def test_span_session_traced_window(fake_profiler, model, mix, tmp_path):
    cell = helpers.tiny_cell(model, mix)
    sess = span_probe.SpanSession(cell, jax.devices()[:1], lambda _: None)
    win = sess.measure(SEED, 0.4, lambda _: None, trace_dir=str(tmp_path))
    assert sess.batcher.spans is None  # detached when the window closed
    (window_ns, reduced), = fake_profiler
    assert tuple(window_ns) == win.window_ns
    bench = [s for s in reduced if s[2].startswith("bench.")]
    program = [s for s in reduced if s[2].startswith("serve.")]
    assert bench == win.bench_spans
    assert program == [r[:3] for r in win.spans]
    w0, w1 = win.window_ns
    flushes = win.counters["flushes"]
    assert flushes > 0
    names = [r[2] for r in win.spans]
    assert names.count("serve.pack") == names.count("serve.dispatch") == \
        flushes
    for row in win.spans:
        assert w0 <= row[0] <= row[1] <= w1
        assert _inside(row, bench, "bench.tick")
    assert len(win.queue_wait_ms) >= flushes
    assert np.all(win.queue_wait_ms >= 0)
    out = span_probe.summary(win)
    assert out["spans_on"] and out["tick_ms_per_flush"] > 0
    assert out["pack_ms_per_flush"] + out["dispatch_ms_per_flush"] <= \
        out["tick_ms_per_flush"]
    assert out["queue_wait_ms"] >= 0
    assert sorted(out["host"]) == ["gc", "majflt", "minflt"]
    assert min(out["host"].values()) >= 0

    sess.record_spans = False
    win = sess.measure(SEED + 1, 0.2, lambda _: None,
                       trace_dir=str(tmp_path))
    assert win.spans is None and sess.batcher.spans is None
    assert all(s[2].startswith("bench.") for s in fake_profiler[-1][1])
    assert span_probe.summary(win)["pack_ms_per_flush"] is None
    assert span_probe.queue_wait_ms(win) is not None

    win = sess.measure(SEED + 2, 0.2, lambda _: None)  # untraced: no log
    assert win.spans is None and win.trace is None
    assert len(fake_profiler) == 2
