"""Shape-bucketed CNN batcher: correctness, bucket policy, jit signatures."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.cnn_batching import CNNBatcher, CNNRequest, batch_bucket


def _mark_fn(x):
    """Batch-position-sensitive toy model: catches pad-row mixups."""
    return jnp.sum(x, axis=tuple(range(1, x.ndim))) + 0.5


def _reqs(shapes, rng):
    return [CNNRequest(rid=i, x=rng.standard_normal(s).astype(np.float32))
            for i, s in enumerate(shapes)]


def test_batch_bucket_policy():
    assert [batch_bucket(n, 8) for n in (1, 2, 3, 5, 8, 11)] == \
        [1, 2, 4, 8, 8, 8]
    assert batch_bucket(3, 4) == 4
    assert batch_bucket(7, 1) == 1


def test_outputs_match_direct_apply():
    rng = np.random.default_rng(0)
    reqs = _reqs([(6, 3)] * 5, rng)
    out = CNNBatcher(_mark_fn, max_batch=4).run(reqs)
    assert len(out) == 5
    for r in reqs:
        assert r.done
        np.testing.assert_allclose(
            out[r.rid], np.asarray(_mark_fn(jnp.asarray(r.x)[None]))[0],
            rtol=1e-6)


def test_pad_rows_discarded_and_counted():
    rng = np.random.default_rng(1)
    b = CNNBatcher(_mark_fn, max_batch=4, max_wait_ticks=0)
    out = b.run(_reqs([(5, 2)] * 3, rng))  # 3 requests pad to a 4-slot flush
    assert len(out) == 3 and b.stats["padded_rows"] == 1
    assert b.stats["flushes"] == 1 and b.stats["served"] == 3


def test_shape_buckets_isolate_and_bound_signatures():
    rng = np.random.default_rng(2)
    shapes = [(4, 3)] * 9 + [(6, 3)] * 2 + [(4, 5)]
    b = CNNBatcher(_mark_fn, max_batch=4, max_wait_ticks=0)
    reqs = _reqs(shapes, rng)
    out = b.run(reqs)
    assert len(out) == len(shapes)
    for r in reqs:  # every request served under its own shape
        np.testing.assert_allclose(
            out[r.rid], np.asarray(_mark_fn(jnp.asarray(r.x)[None]))[0],
            rtol=1e-6)
    # (4,3): flushes of 4,4,1 -> slots {4,1}; (6,3): slots {2}; (4,5): {1}
    assert b.n_signatures == 4
    assert b.stats["flushes"] == 5


def test_partial_bucket_waits_then_flushes():
    rng = np.random.default_rng(3)
    b = CNNBatcher(_mark_fn, max_batch=8, max_wait_ticks=2)
    b.submit(_reqs([(3, 3)] * 2, rng))
    assert b.tick() == 0  # age 1: below max_batch, within latency bound
    assert b.tick() == 0  # age 2
    assert b.tick() == 2  # age 3 > max_wait_ticks: partial flush
    assert b.pending() == 0


def test_wait_clock_resets_after_drain():
    """A flush from drain() must restart the bucket's wait clock — the next
    lone request gets the full max_wait_ticks to find batchmates."""
    rng = np.random.default_rng(5)
    b = CNNBatcher(_mark_fn, max_batch=8, max_wait_ticks=3)
    b.submit(_reqs([(3, 3)], rng))
    for _ in range(3):
        b.tick()
    b.drain()
    b.submit(_reqs([(3, 3)], rng))
    assert b.tick() == 0  # fresh clock: not flushed prematurely
    assert b.pending() == 1


def test_drain_flushes_everything_now():
    rng = np.random.default_rng(4)
    b = CNNBatcher(_mark_fn, max_batch=8, max_wait_ticks=50)
    b.submit(_reqs([(3, 3)] * 3 + [(2, 2)] * 2, rng))
    assert b.drain() == 5
    assert b.pending() == 0 and b.stats["served"] == 5


def test_kws_int_apply_served_matches_direct():
    """End-to-end: the batcher over kws.int_serve_fn reproduces unbatched
    int_apply bit-for-bit (pad rows don't leak into real outputs)."""
    from conftest import trained_int_params
    from repro.core.quant import QuantConfig
    from repro.models import kws
    cfg = kws.KWSConfig.reduced()
    qcfg = QuantConfig(2, 4, 4, fq=True)
    _, _, ip = trained_int_params(
        kws, cfg, [f"conv{i}" for i in range(len(cfg.dilations))], qcfg)
    fn = kws.int_serve_fn(ip, qcfg, cfg)

    rng = np.random.default_rng(7)
    xs = rng.standard_normal((3, cfg.seq_len, cfg.n_mfcc)).astype(np.float32)
    reqs = [CNNRequest(rid=i, x=xs[i]) for i in range(3)]
    out = CNNBatcher(fn, max_batch=4, max_wait_ticks=0).run(reqs)
    direct = np.asarray(kws.int_apply(ip, jnp.asarray(xs), qcfg, cfg))
    for i in range(3):
        np.testing.assert_allclose(out[i], direct[i], rtol=0, atol=1e-5)


def _kws_serve_setup():
    from conftest import trained_int_params
    from repro.core.quant import QuantConfig
    from repro.models import kws
    cfg = kws.KWSConfig.reduced()
    qcfg = QuantConfig(2, 4, 4, fq=True)
    _, _, ip = trained_int_params(
        kws, cfg, [f"conv{i}" for i in range(len(cfg.dilations))], qcfg)
    return kws.int_serve_fn(ip, qcfg, cfg), cfg


def test_noise_canary_zero_sigma_is_clean_path():
    """noise_config=None and NoiseConfig(0,0,0) are the SAME serving
    path: bit-identical outputs, no noise trials counted."""
    from repro.core.noise import NoiseConfig
    fn, cfg = _kws_serve_setup()
    rng = np.random.default_rng(11)
    xs = rng.standard_normal((5, cfg.seq_len, cfg.n_mfcc)).astype(np.float32)
    out0 = CNNBatcher(fn, max_batch=4, max_wait_ticks=0).run(
        [CNNRequest(rid=i, x=xs[i]) for i in range(5)])
    bz = CNNBatcher(fn, max_batch=4, max_wait_ticks=0,
                    noise_config=NoiseConfig(0.0, 0.0, 0.0))
    outz = bz.run([CNNRequest(rid=i, x=xs[i]) for i in range(5)])
    for i in range(5):
        np.testing.assert_array_equal(out0[i], outz[i])
    assert bz.stats["noise_trials"] == 0


def test_noise_canary_perturbs_and_counts_trials():
    """A noisy canary tier serves perturbed outputs, counts one noise
    trial per flush, and replays bit-exact from the same noise_seed."""
    from repro.core.noise import TABLE7_CONDITIONS
    fn, cfg = _kws_serve_setup()
    rng = np.random.default_rng(12)
    xs = rng.standard_normal((6, cfg.seq_len, cfg.n_mfcc)).astype(np.float32)
    clean = CNNBatcher(fn, max_batch=4, max_wait_ticks=0).run(
        [CNNRequest(rid=i, x=xs[i]) for i in range(6)])

    def canary():
        b = CNNBatcher(fn, max_batch=4, max_wait_ticks=0,
                       noise_config=TABLE7_CONDITIONS[-1], noise_seed=5)
        return b, b.run([CNNRequest(rid=i, x=xs[i]) for i in range(6)])

    b1, out1 = canary()
    assert b1.stats["noise_trials"] == b1.stats["flushes"] == 2
    assert any(not np.array_equal(clean[i], out1[i]) for i in range(6))
    b2, out2 = canary()  # same seed -> same canary outputs
    for i in range(6):
        np.testing.assert_array_equal(out1[i], out2[i])
    assert b2.stats["noise_trials"] == 2


def test_noise_canary_flush_keys_differ():
    """Two flushes of the SAME payload under a noise canary draw
    different per-flush keys (trial-indexed), so repeated canary probes
    sample the noise distribution rather than replaying one draw."""
    from repro.core.noise import TABLE7_CONDITIONS
    fn, cfg = _kws_serve_setup()
    rng = np.random.default_rng(13)
    x = rng.standard_normal((cfg.seq_len, cfg.n_mfcc)).astype(np.float32)
    b = CNNBatcher(fn, max_batch=1, max_wait_ticks=0,
                   noise_config=TABLE7_CONDITIONS[-1], noise_seed=9)
    out = b.run([CNNRequest(rid=0, x=x.copy()), CNNRequest(rid=1, x=x.copy())])
    assert b.stats["noise_trials"] == 2
    assert not np.array_equal(out[0], out[1])


def test_bucket_state_garbage_collected():
    """Regression (ISSUE 3): empty _queues/_age entries must not persist
    after drain — high shape cardinality would grow bucket state forever."""
    rng = np.random.default_rng(6)
    b = CNNBatcher(_mark_fn, max_batch=4, max_wait_ticks=0)
    b.run(_reqs([(n, 2) for n in range(2, 42)], rng))  # 40 distinct shapes
    assert b._queues == {} and b._age == {}
    assert b.stats["served"] == 40
    # ...and buckets emptied by tick() are collected too, not just drain()
    b.submit(_reqs([(3, 3)], rng))
    b.tick()
    assert b._queues == {} and b._age == {}


def test_sync_tick_flushes_one_bucket_per_quantum():
    """Sync mode: the blocking device_get consumes the host quantum, so a
    tick performs at most one flush; the rest age into later ticks."""
    rng = np.random.default_rng(7)
    b = CNNBatcher(_mark_fn, max_batch=2, max_wait_ticks=0)
    b.submit(_reqs([(2, 2)] * 2 + [(3, 3)] * 2 + [(4, 4)] * 2, rng))
    assert b.tick() == 2 and b.stats["flushes"] == 1
    assert b.tick() == 2 and b.tick() == 2
    assert b.pending() == 0


def test_priority_age_beats_fill():
    """A starved odd-shape bucket must outrank a perpetually-full hot
    bucket once its age pulls ahead (the (age, fill) ranking)."""
    rng = np.random.default_rng(8)
    b = CNNBatcher(_mark_fn, max_batch=2, max_wait_ticks=5)
    odd = _reqs([(3, 3)], rng)
    b.submit(odd)
    done_at = None
    for t in range(12):  # hot bucket refills every tick, always full
        b.submit([CNNRequest(rid=100 + t * 2 + i,
                             x=rng.standard_normal((2, 2)).astype(np.float32))
                  for i in range(2)])
        b.tick()
        if odd[0].done and done_at is None:
            done_at = t
    assert done_at is not None and done_at <= 8, done_at
    assert odd[0].wait_ticks <= 8


def test_dispatch_ahead_resolves_next_tick():
    rng = np.random.default_rng(9)
    b = CNNBatcher(_mark_fn, max_batch=2, max_wait_ticks=0,
                   dispatch_ahead=True, max_inflight=2)
    reqs = _reqs([(2, 2)] * 2, rng)
    b.submit(reqs)
    assert b.tick() == 0            # dispatched, parked in flight
    assert b.in_flight == 2 and not reqs[0].done
    assert b.tick() == 2            # resolved one quantum later
    assert all(r.done for r in reqs)
    np.testing.assert_allclose(
        reqs[0].out, np.asarray(_mark_fn(jnp.asarray(reqs[0].x)[None]))[0],
        rtol=1e-6)


def test_dispatch_ahead_window_backpressure():
    """With a 1-slot in-flight window and 3 hungry buckets, dispatches are
    back-pressured into later ticks and counted."""
    rng = np.random.default_rng(10)
    b = CNNBatcher(_mark_fn, max_batch=2, max_wait_ticks=0,
                   dispatch_ahead=True, max_inflight=1)
    b.submit(_reqs([(2, 2)] * 2 + [(3, 3)] * 2 + [(4, 4)] * 2, rng))
    b.tick()
    assert b.stats["flushes"] == 1 and b.stats["window_waits"] == 1
    assert b.stats["inflight_peak"] == 1
    for _ in range(6):
        b.tick()
    assert b.stats["served"] == 6 and b.outstanding() == 0


def test_dispatch_ahead_fewer_ticks_than_sync():
    """The acceptance property on a toy trace: under multi-bucket
    contention, dispatch-ahead serves the same trace in strictly fewer
    scheduler quanta than sync."""
    def replay(dispatch_ahead):
        rng = np.random.default_rng(11)
        b = CNNBatcher(_mark_fn, max_batch=2, max_wait_ticks=1,
                       dispatch_ahead=dispatch_ahead, max_inflight=4)
        rid, ticks = 0, 0
        for _ in range(3):  # 3 arrival ticks x 3 buckets x full batch
            rs = []
            for shape in ((2, 2), (3, 3), (4, 4)):
                for _ in range(2):
                    rs.append(CNNRequest(
                        rid=rid,
                        x=rng.standard_normal(shape).astype(np.float32)))
                    rid += 1
            b.submit(rs)
            b.tick()
            ticks += 1
        while b.outstanding() and ticks < 100:
            b.tick()
            ticks += 1
        assert b.outstanding() == 0 and b.stats["served"] == 18
        return ticks

    assert replay(True) < replay(False)


def test_drain_resolves_inflight():
    rng = np.random.default_rng(12)
    b = CNNBatcher(_mark_fn, max_batch=8, max_wait_ticks=50,
                   dispatch_ahead=True, max_inflight=2)
    reqs = _reqs([(3, 3)] * 5 + [(2, 2)] * 3, rng)
    b.submit(reqs)
    assert b.drain() == 8
    assert all(r.done for r in reqs) and b.in_flight == 0
    assert b._queues == {} and b._age == {}


def test_wait_tick_stats_exposed():
    rng = np.random.default_rng(13)
    b = CNNBatcher(_mark_fn, max_batch=8, max_wait_ticks=2)
    b.submit(_reqs([(3, 3)] * 2, rng))
    for _ in range(3):
        b.tick()  # flushes on the 3rd tick -> wait 2
    ws = b.stats["wait_ticks"]
    (label, st), = ws.items()
    assert "(3, 3)" in label and st["n"] == 2
    assert st["p50"] == 2.0 and st["p99"] == 2.0 and st["max"] == 2


def test_wait_tick_stats_windowed_not_history_diluted():
    """Satellite bugfix (ISSUE 10): lifetime percentiles dilute a recent
    latency regression under old healthy history; ``wait_ticks_recent``
    covers only the last ``wait_window`` samples, so the fleet SLO check
    sees the regression era, not the average of both."""
    rng = np.random.default_rng(113)
    b = CNNBatcher(_mark_fn, max_batch=2, max_wait_ticks=4, wait_window=8)
    for i in range(16):  # healthy era: full buckets, zero wait
        b.submit(_reqs([(3, 3)] * 2, rng))
        b.tick()
    for i in range(8):   # regression era: singletons age 4 ticks
        b.submit(_reqs([(3, 3)], rng))
        for _ in range(5):
            b.tick()
    label, = b.stats["wait_ticks"].keys()
    life = b.stats["wait_ticks"][label]
    recent = b.stats["wait_ticks_recent"][label]
    assert life["n"] == 40 and life["p50"] == 0.0  # diluted: looks healthy
    assert recent["n"] == 8                        # bounded window
    assert recent["p50"] == recent["max"] == 4     # the regression, visible
    assert b.wait_stats(window=True) is b.stats["wait_ticks_recent"]  # cached


def test_ladder_integration_normalizes_and_counts():
    from repro.serve.shape_ladder import LadderSpec, ShapeLadder
    rng = np.random.default_rng(14)
    lad = ShapeLadder(LadderSpec("frames", (6,), 3))
    b = CNNBatcher(_mark_fn, max_batch=4, max_wait_ticks=0, ladder=lad)
    reqs = _reqs([(4, 3), (6, 3), (9, 3), (5, 7)], rng)  # last: miss
    out = b.run(reqs)
    assert len(out) == 4
    st = b.stats
    assert st["ladder_hits"] == 3 and st["ladder_misses"] == 1
    assert st["ladder_normalized"] == 2  # (4,3) padded, (9,3) cropped
    # hits share ONE shape bucket; the miss keeps its own
    assert {k[0] for k in b._signatures} == {((6, 3), "<f4"), ((5, 7), "<f4")}
    for r in reqs:  # outputs are for the SERVED (normalized) payload
        np.testing.assert_allclose(
            out[r.rid],
            np.asarray(_mark_fn(jnp.asarray(r.x_served)[None]))[0],
            rtol=1e-6)


def test_continuous_batcher_queue_initialized():
    """serve/batching.ContinuousBatcher owns _queue from __init__ (no
    getattr-lazy init at call sites)."""
    from repro.models import transformer as T
    from repro.core.quant import QuantConfig
    from repro.serve.batching import ContinuousBatcher
    cfg = T.TransformerConfig(
        name="tiny", n_layers=1, d_model=16, n_heads=2, n_kv_heads=1,
        d_ff=32, vocab=32, param_dtype=jnp.float32, max_seq=32)
    b = ContinuousBatcher(T.make_params(jax.random.key(0), cfg), cfg,
                          QuantConfig(8, 8), slots=2, max_len=16)
    assert b._queue == []


def test_stats_expose_fault_and_age_counters():
    """ISSUE 7 satellite: per-flush retry/shed/in-flight-age counters in
    stats(), and the swap-generation stamp on every result."""
    from repro.serve.faults import FaultPlan, FaultyDevice
    plan = FaultPlan(seed=9, p_flush_fail=0.5, p_stuck=0.6,
                     max_stuck_ticks=3, max_retries=2, backoff_ticks=1)
    b = CNNBatcher(_mark_fn, max_batch=2, max_wait_ticks=0,
                   dispatch_ahead=True, max_inflight=2,
                   device=FaultyDevice(plan))
    rng = np.random.default_rng(3)
    reqs = _reqs([(6, 3)] * 10, rng)
    b.submit(reqs)
    for _ in range(60):
        if not b.outstanding():
            break
        b.tick()
    b.drain()
    st = b.stats
    for k in ("flush_faults", "retries", "stuck_flushes", "shed"):
        assert k in st and st[k] >= 0
    assert st["flush_faults"] > 0 and st["retries"] > 0
    age = st["inflight_age"]
    assert age["n"] > 0 and age["max"] >= 1  # stuck results aged
    assert age["mean"] <= age["max"]
    assert st["served"] + st["shed"] == len(reqs)


def test_results_carry_generation_stamp():
    """Every served result records the swap generation that computed it;
    the stamp is applied at FLUSH time, not submit time."""
    b = CNNBatcher(_mark_fn, max_batch=4, max_wait_ticks=0)
    rng = np.random.default_rng(4)
    first = _reqs([(6, 3)] * 2, rng)
    b.submit(first)
    b.drain()
    b.swap_apply_fn(lambda x: _mark_fn(x) + 1.0)
    b.swap_apply_fn(lambda x: _mark_fn(x) + 2.0)
    second = [CNNRequest(rid=10 + i,
                         x=rng.standard_normal((6, 3)).astype(np.float32))
              for i in range(2)]
    b.submit(second)
    b.drain()
    assert b.generation == 2 and b.stats["generation"] == 2
    assert all(r.generation == 0 for r in first)
    assert all(r.generation == 2 for r in second)


def test_span_log_keeps_rows():
    import time
    from repro.serve.spans import SpanLog
    log = SpanLog()
    t = time.time_ns()
    log.record(t, "a", k=1)
    log.record(0, "b")
    rows = log.rows()
    assert [(r[0], r[2], r[3]) for r in rows] == [(t, "a", {"k": 1}),
                                                  (0, "b", {})]
    assert t <= rows[0][1] <= rows[1][1]
    rows.clear()  # a copy
    assert len(log.rows()) == 2


_SPAN_MODES = {
    "sync": dict(max_batch=4, max_wait_ticks=1),
    "dispatch_ahead": dict(max_batch=4, max_wait_ticks=1,
                           dispatch_ahead=True, max_inflight=2),
    "two_lanes": dict(max_batch=4, max_wait_ticks=1, dispatch_ahead=True,
                      max_inflight=1, n_replicas=2),
}


def _serve_mixed(mode, spans=None):
    """A mixed trace (two shapes, partial batches) served tick by tick
    with arrivals between ticks; returns requests, stats and the event
    stream with each request named by its id."""
    events = []

    def on_event(etype, kw):
        kw = dict(kw)
        if "reqs" in kw:
            kw["reqs"] = [r.rid for r in kw["reqs"]]
        events.append((etype, kw))

    b = CNNBatcher(_mark_fn, on_event=on_event, **_SPAN_MODES[mode])
    b.spans = spans
    rng = np.random.default_rng(13)
    reqs = _reqs([(6, 3)] * 7 + [(4, 5)] * 3, rng)
    for i in range(0, len(reqs), 3):
        b.submit(reqs[i:i + 3])
        b.tick()
    for _ in range(6):
        b.tick()
    b.drain()
    assert all(r.done and r.error is None for r in reqs)
    return reqs, b.stats, events


@pytest.mark.parametrize("mode", sorted(_SPAN_MODES))
def test_flush_spans_link_by_flush_id(mode):
    from repro.serve.spans import SpanLog
    log = SpanLog()
    reqs, stats, _ = _serve_mixed(mode, spans=log)
    by_flush = {}
    for s, e, name, attrs in log.rows():
        assert s <= e
        by_flush.setdefault(attrs["flush"], {}).setdefault(
            name, []).append((s, e, attrs))
    assert sorted(by_flush) == list(range(stats["flushes"]))
    lanes = set()
    for fid, spans in by_flush.items():
        assert {k: len(v) for k, v in spans.items()} == {
            "serve.pack": 1, "serve.dispatch": 1, "serve.resolve": 1}, fid
        (p0, p1, pack), = spans["serve.pack"]
        (d0, d1, disp), = spans["serve.dispatch"]
        (r0, r1, res), = spans["serve.resolve"]
        assert p1 <= d0 and d1 <= r0
        assert 1 <= pack["n"] <= pack["slots"] <= 4
        assert pack["bytes"] in (pack["slots"] * 6 * 3 * 4,
                                 pack["slots"] * 4 * 5 * 4)  # float32 rows
        assert disp["lane"] == res["lane"]
        assert res["age_ticks"] >= 0 and (res["age_ticks"] == 0
                                          or mode != "sync")
        lanes.add(disp["lane"])
    assert lanes == set(range(_SPAN_MODES[mode].get("n_replicas", 1)))
    assert sum(v["serve.pack"][0][2]["n"] for v in by_flush.values()) == \
        len(reqs)
    resolved = {attrs["flush"]: s for s, _, name, attrs in log.rows()
                if name == "serve.resolve"}
    packed = {s: attrs["flush"] for s, _, name, attrs in log.rows()
              if name == "serve.pack"}
    for r in reqs:
        assert 0 <= r.submit_ns <= r.dispatch_ns
        assert r.wait_ms == (r.dispatch_ns - r.submit_ns) / 1e6 >= 0
        # a request's flush starts packing at its dispatch stamp
        assert r.dispatch_ns <= resolved[packed[r.dispatch_ns]]


@pytest.mark.parametrize("mode", sorted(_SPAN_MODES))
def test_span_log_changes_no_output_stat_or_event(mode):
    from repro.serve.spans import SpanLog
    bare, bare_stats, bare_events = _serve_mixed(mode)
    spanned, stats, events = _serve_mixed(mode, spans=SpanLog())
    for a, b in zip(bare, spanned):
        np.testing.assert_array_equal(a.out, b.out)
        assert (a.wait_ticks, a.finish_tick, a.generation) == \
            (b.wait_ticks, b.finish_tick, b.generation)
    assert stats == bare_stats
    assert events == bare_events


# -- dispatch-ahead: where in a tick a ready flush resolves ------------------


def _ordered(**kw):
    """A batcher on _mark_fn that logs its flush/resolve events and spans
    in one stream, in call order: (kind, flush id or None, lane)."""
    from repro.serve.spans import SpanLog
    stream = []

    class Log(SpanLog):
        __slots__ = ()

        def record(self, start_ns, name, **attrs):
            stream.append((name, attrs["flush"], attrs.get("lane")))

    def on_event(etype, ev):
        if etype in ("flush", "resolve"):
            stream.append((etype, None, ev["replica"]))

    b = CNNBatcher(_mark_fn, on_event=on_event, **kw)
    b.spans = Log()
    return b, stream


def _one(rng, rid0, n):
    return [CNNRequest(rid=rid0 + i,
                       x=rng.standard_normal((2, 2)).astype(np.float32))
            for i in range(n)]


def test_full_window_resolves_just_before_the_flush_that_reuses_it():
    """One lane, a full window of ready flushes, two flushes due: resolve
    0, flush 2, resolve 1, flush 3 — never two flushes unfetched behind
    an empty device queue."""
    rng = np.random.default_rng(21)
    b, stream = _ordered(max_batch=1, max_wait_ticks=0,
                         dispatch_ahead=True, max_inflight=2)
    first = _one(rng, 0, 2)
    b.submit(first)
    assert b.tick() == 0
    del stream[:]
    second = _one(rng, 2, 2)
    b.submit(second)
    assert b.tick() == 2
    kinds = [(k, f) for k, f, _ in stream if k != "serve.dispatch"]
    assert kinds == [
        ("serve.resolve", 0), ("resolve", None),
        ("serve.pack", 2), ("flush", None),
        ("serve.resolve", 1), ("resolve", None),
        ("serve.pack", 3), ("flush", None)]
    st = b.stats
    assert st["deferred_resolves"] == 2 and st["inflight_peak"] == 2
    assert st["replicas"][0]["inflight_peak"] == 2
    assert all(r.finish_tick == 1 for r in first)
    assert all(r.wait_ticks == 0 and not r.done for r in second)
    assert b.tick() == 2 and all(r.finish_tick == 2 for r in second)
    assert b.stats["deferred_resolves"] == 2  # window not full: at start
    assert b.stats["inflight_age"]["max"] == 1


def test_free_slot_resolves_before_any_pack():
    """A lane with a free slot fetches its ready flush at the start of
    the tick, as under-loaded traffic always did; a full lane that no
    flush reaches resolves its due flushes at the end of the tick."""
    rng = np.random.default_rng(22)
    b, stream = _ordered(max_batch=1, max_wait_ticks=0,
                         dispatch_ahead=True, max_inflight=2)
    b.submit(_one(rng, 0, 1))
    b.tick()
    del stream[:]
    b.submit(_one(rng, 1, 2))
    assert b.tick() == 1
    kinds = [k for k, _, _ in stream if k != "serve.dispatch"]
    assert kinds == ["serve.resolve", "resolve", "serve.pack", "flush",
                     "serve.pack", "flush"]
    assert b.stats["deferred_resolves"] == 0
    del stream[:]
    assert b.tick() == 2  # full window, nothing to flush: resolved late
    assert [k for k, _, _ in stream] == ["serve.resolve", "resolve"] * 2
    assert b.stats["deferred_resolves"] == 0 and b.outstanding() == 0


def test_deferred_resolves_keep_routing_and_peak():
    """Two lanes: lane 0 full of ready flushes, lane 1 one ready flush.
    Routing and the in-flight peak count due flushes as resolved, so the
    three flushes land on lanes 1, 0, 1 with a peak of three, as when
    every ready flush resolved first; lane 0 displaces one due flush and
    resolves the other at the end of the tick."""
    rng = np.random.default_rng(23)
    b, stream = _ordered(max_batch=1, max_wait_ticks=0,
                         dispatch_ahead=True, max_inflight=2, n_replicas=2)
    b.submit(_one(rng, 0, 3))
    b.tick()
    assert [l["inflight"] for l in b.stats["replicas"]] == [2, 1]
    del stream[:]
    b.submit(_one(rng, 3, 3))
    assert b.tick() == 3
    events = [(k, lane) for k, _, lane in stream if k in ("flush",
                                                          "resolve")]
    assert events == [("resolve", 1), ("flush", 1), ("resolve", 0),
                      ("flush", 0), ("flush", 1), ("resolve", 0)]
    st = b.stats
    assert st["deferred_resolves"] == 1
    assert st["inflight_peak"] == 3
    assert [l["inflight_peak"] for l in st["replicas"]] == [2, 2]
    assert [l["inflight"] for l in st["replicas"]] == [1, 2]


def test_stuck_head_is_not_resolved_early():
    """A flush the fault layer holds past its tick is not ready: its full
    window takes no flush and nothing behind it resolves until it is."""
    from repro.serve.faults import FlushFate

    class StuckFirst:
        max_retries, backoff_ticks = 3, 1

        def __init__(self):
            self.calls = 0

        def flush_fate(self, *, tick=-1):
            self.calls += 1
            return FlushFate(False, 2 if self.calls == 1 else 0, -1)

    rng = np.random.default_rng(24)
    b, stream = _ordered(max_batch=1, max_wait_ticks=0,
                         dispatch_ahead=True, max_inflight=2,
                         device=StuckFirst())
    first = _one(rng, 0, 2)
    b.submit(first)
    b.tick()                                  # tick 0: flush 0 stuck 2
    b.submit(_one(rng, 2, 2))
    del stream[:]
    assert b.tick() == 0 and b.tick() == 0    # ticks 1, 2: head not ready
    assert stream == [] and b.stats["window_waits"] == 2
    assert b.tick() == 2                      # tick 3: both due, displaced
    assert [r.finish_tick for r in first] == [3, 3]
    assert b.stats["deferred_resolves"] == 2
    assert b.stats["inflight_age"]["max"] == 3


@pytest.mark.parametrize("n_replicas", [1, 2])
def test_sync_mode_defers_no_resolve(n_replicas):
    rng = np.random.default_rng(25)
    b, stream = _ordered(max_batch=1, max_wait_ticks=0,
                         n_replicas=n_replicas)
    reqs = _one(rng, 0, 4)
    b.submit(reqs)
    for _ in range(4):
        assert b.tick() == 1
    assert [k for k, _, _ in stream if k in ("flush", "resolve")] == \
        ["flush", "resolve"] * 4
    assert b.stats["deferred_resolves"] == 0
    assert [r.finish_tick for r in reqs] == [0, 1, 2, 3]
