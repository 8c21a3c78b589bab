"""Fuzz/parity sweep for the CNN batcher (ISSUE 3, foregrounded satellite).

Seeded random arrival schedules — mixed shapes, dtypes, burst sizes,
interleaved submit/tick/drain — must serve every request exactly once,
bit-exact vs calling ``apply_fn`` per request unbatched, in BOTH flush
modes (sync and dispatch-ahead), with and without a shape ladder.

The toy model rounds inputs onto an integer lattice and reduces in int32,
so batched and unbatched evaluations are bit-identical by construction and
every comparison is exact equality (no tolerance hiding a pad-row leak).
One module-level jitted step is shared across every batcher instance so
the ~30 (shape, slots) signatures compile once for the whole sweep.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import mesh as mesh_mod
from repro.serve.cnn_batching import CNNBatcher, CNNRequest
from repro.serve.shape_ladder import LadderSpec, ShapeLadder


def _toy(x):
    """Batch-position-sensitive, integer-exact per-row model."""
    xi = jnp.round(x.astype(jnp.float32) * 8.0).astype(jnp.int32)
    axes = tuple(range(1, x.ndim))
    return jnp.sum(xi * xi, axis=axes) * 3 + jnp.max(xi, axis=axes)


_STEP = jax.jit(_toy)  # shared compile cache across all fuzz batchers

_SHAPES = [(5, 3), (4, 4), (7, 2), (3, 3, 2), (6,)]

# ladder sweep: rank-2 feat-3 frames + rank-3 channel-2 planes are rungs;
# feat-4 payloads are deliberate ladder misses (served raw)
_LADDER = ShapeLadder(LadderSpec("frames", (5, 8), 3),
                      LadderSpec("image", (6,), 2))
_LADDER_SHAPES = [(3, 3), (5, 3), (7, 3), (9, 3),      # frames hits
                  (4, 5, 2), (7, 7, 2), (8, 3, 2),     # image hits
                  (4, 4)]                              # feat-4 miss


def _mk_request(rng, rid, shapes):
    shape = shapes[int(rng.integers(len(shapes)))]
    if rng.random() < 0.4:
        x = rng.integers(-8, 8, size=shape).astype(np.int8)
    else:
        x = rng.standard_normal(shape).astype(np.float32)
    return CNNRequest(rid=rid, x=x)


def _run_schedule(seed, dispatch_ahead, *, ladder=None, shapes=_SHAPES,
                  n_ops=14, n_replicas=1):
    rng = np.random.default_rng(seed)
    b = CNNBatcher(
        _toy, max_batch=int(rng.choice([2, 4, 8])),
        max_wait_ticks=int(rng.integers(0, 4)),
        dispatch_ahead=dispatch_ahead,
        max_inflight=int(rng.integers(1, 5)),
        ladder=ladder, step_fn=_STEP,  # shared across lanes: the
        # CPU-simulation mode (and the shared compile cache)
        n_replicas=n_replicas,
        replica_devices=(mesh_mod.replica_devices(n_replicas)
                         if n_replicas > 1 else None))
    reqs = []
    for _ in range(n_ops):
        op = rng.random()
        if op < 0.55:
            burst = int(rng.integers(1, 5))  # burst size 1..4
            rs = [_mk_request(rng, len(reqs) + i, shapes)
                  for i in range(burst)]
            b.submit(rs)
            reqs.extend(rs)
        elif op < 0.9:
            b.tick()
        else:
            b.drain()
    for guard in range(500):
        if not b.outstanding():
            break
        b.tick()
    assert not b.outstanding(), f"seed {seed}: requests stuck"
    b.drain()  # idempotent on empty state
    return b, reqs


def _check_schedule(b, reqs, seed):
    assert len({r.rid for r in reqs}) == len(reqs)
    assert b.stats["served"] == len(reqs), seed
    for r in reqs:
        assert r.done, (seed, r.rid)
        want = np.asarray(_toy(jnp.asarray(r.x_served)[None]))[0]
        assert np.array_equal(np.asarray(r.out), want), (seed, r.rid)
        assert r.wait_ticks >= 0
    # dead buckets are garbage-collected once drained
    assert b._queues == {} and b._age == {}, seed
    assert not b._inflight


@pytest.mark.parametrize("dispatch_ahead", [False, True])
def test_fuzz_schedules_bit_exact(dispatch_ahead):
    """>= 100 seeded schedules per flush mode (200+ across the sweep)."""
    for seed in range(110):
        b, reqs = _run_schedule(seed, dispatch_ahead)
        _check_schedule(b, reqs, seed)


@pytest.mark.parametrize("dispatch_ahead", [False, True])
def test_fuzz_schedules_with_ladder(dispatch_ahead):
    """Laddered schedules: parity is against the NORMALIZED payload
    (r.x_served), misses serve raw, and the jit-signature count respects
    the ladder bound plus one bucket family per missed shape."""
    slots = {2: 2, 4: 3, 8: 4}
    for seed in range(40):
        b, reqs = _run_schedule(1000 + seed, dispatch_ahead,
                                ladder=_LADDER, shapes=_LADDER_SHAPES)
        _check_schedule(b, reqs, 1000 + seed)
        st = b.stats
        assert st["ladder_hits"] + st["ladder_misses"] == len(reqs)
        rungs = set(_LADDER.shapes)
        for r in reqs:  # every contract-matching request landed ON a rung
            if _LADDER.spec_for(np.asarray(r.x).shape) is not None:
                assert tuple(r.x_served.shape) in rungs, (seed, r.rid)
            else:  # misses serve raw, untouched
                assert r.x_served.shape == np.asarray(r.x).shape
        miss_families = len({(tuple(r.x_served.shape), r.x_served.dtype.str)
                             for r in reqs
                             if tuple(r.x_served.shape) not in rungs})
        bound = (len(_LADDER.shapes) * 2 + miss_families) \
            * slots[b.max_batch]  # x2: float32 and int8 code payloads
        assert b.n_signatures <= bound, (seed, b.n_signatures, bound)


def test_modes_agree_bit_exact():
    """The same schedule served in both modes yields identical outputs —
    dispatch-ahead changes WHEN results land, never what they are."""
    for seed in (7, 21, 63):
        _, r_sync = _run_schedule(seed, False)
        _, r_async = _run_schedule(seed, True)
        assert len(r_sync) == len(r_async)
        for a, c in zip(r_sync, r_async):
            assert np.array_equal(np.asarray(a.out), np.asarray(c.out))


@pytest.mark.mesh
@pytest.mark.parametrize("dispatch_ahead", [False, True])
def test_fuzz_multi_replica_bit_exact(dispatch_ahead):
    """Replica-lane sweep (ISSUE 10): seeded schedules × {1, 2, 4}
    replicas. Every replica count must serve exactly-once, bit-exact vs
    the unbatched apply_fn, AND byte-identical to the 1-replica run of
    the same schedule — routing may only move work between lanes, never
    change what any request computes."""
    for seed in range(25):
        outs_by_n = {}
        for n in (1, 2, 4):
            b, reqs = _run_schedule(3000 + seed, dispatch_ahead,
                                    n_replicas=n)
            _check_schedule(b, reqs, (3000 + seed, n))
            st = b.stats
            assert st["n_replicas"] == n and len(st["replicas"]) == n
            assert sum(l["flushes"] for l in st["replicas"]) \
                == st["flushes"], (seed, n)
            assert sum(l["served"] for l in st["replicas"]) \
                == st["served"], (seed, n)
            assert all(l["inflight"] == 0 for l in st["replicas"])
            outs_by_n[n] = [np.asarray(r.out) for r in reqs]
        for n in (2, 4):  # replica-count invariance, byte for byte
            assert len(outs_by_n[n]) == len(outs_by_n[1])
            for a, c in zip(outs_by_n[1], outs_by_n[n]):
                assert np.array_equal(a, c), (seed, n)


def test_double_submit_rejected():
    b = CNNBatcher(_toy, max_batch=2, step_fn=_STEP)
    r = CNNRequest(rid=0, x=np.ones((5, 3), np.float32))
    b.submit([r])
    with pytest.raises(ValueError):
        b.submit([r])
    b.drain()
    with pytest.raises(ValueError):  # done requests can't be resubmitted
        b.submit([r])
    # intake is all-or-nothing: a bad list member must not leave earlier
    # members of the same call silently enqueued
    fresh = CNNRequest(rid=1, x=np.ones((5, 3), np.float32))
    with pytest.raises(ValueError):
        b.submit([fresh, r])
    assert b.pending() == 0 and fresh.x_served is None
    b.submit([fresh])  # a clean retry of the fresh request succeeds
    assert b.pending() == 1
    b.drain()


def test_submit_rejects_duplicate_in_one_call():
    """The same request object twice in ONE submit() list must be
    rejected up front — double-enqueueing would crash the scheduler at
    flush time with inconsistent stats."""
    b = CNNBatcher(_toy, max_batch=2, step_fn=_STEP)
    r = CNNRequest(rid=0, x=np.ones((5, 3), np.float32))
    r2 = CNNRequest(rid=1, x=np.ones((5, 3), np.float32))
    with pytest.raises(ValueError):
        b.submit([r, r2, r])
    assert b.pending() == 0 and r.x_served is None and r2.x_served is None
    b.submit([r, r2])
    assert b.drain() == 2


def test_submit_atomic_on_malformed_payload():
    """A payload that fails np.asarray mid-list must not leave earlier
    list members enqueued (all-or-nothing intake)."""
    b = CNNBatcher(_toy, max_batch=2, step_fn=_STEP)
    good = CNNRequest(rid=0, x=np.ones((5, 3), np.float32))
    bad = CNNRequest(rid=1, x=[[1.0, 2.0], [3.0]])  # ragged
    with pytest.raises(ValueError):
        b.submit([good, bad])
    assert b.pending() == 0 and good.x_served is None
    b.submit([good])  # the good request is cleanly retryable
    assert b.pending() == 1


# -- fault + hot-swap fuzz (ISSUE 7 tentpole) --------------------------------
#
# The same exactly-once contract, now with the device boundary wrapped in
# a seeded FaultPlan (flush failures + stuck in-flight results) and random
# hot-swaps/deadline-sheds interleaved. Every submitted request must end
# DONE in exactly one of two terminal states:
#   * served: bit-exact vs the generation it was flushed under;
#   * shed: a structured error (deadline / flush-fault) and no output.

from repro.serve.faults import FaultPlan, FaultyDevice


def _gen_toy(g):
    """The fuzz model family: generation g is observable in the output,
    so a request served under the wrong generation fails exact parity."""
    def fn(x, noise=None, rng=None):
        xi = jnp.round(x.astype(jnp.float32) * 8.0).astype(jnp.int32)
        axes = tuple(range(1, x.ndim))
        return jnp.sum(xi * xi, axis=axes) * (3 + g) \
            + jnp.max(xi, axis=axes) - g
    return fn


_GEN_STEPS = {}  # shared jit cache: one compile per generation


def _gen_step(g):
    if g not in _GEN_STEPS:
        _GEN_STEPS[g] = jax.jit(_gen_toy(g))
    return _GEN_STEPS[g]


def _run_fault_schedule(seed, dispatch_ahead, *, n_ops=18):
    rng = np.random.default_rng(seed)
    plan = FaultPlan(seed=seed, p_flush_fail=float(rng.choice([0.2, 0.4])),
                     p_stuck=float(rng.choice([0.0, 0.3])),
                     max_stuck_ticks=2, p_canary_corrupt=0.0,
                     max_retries=int(rng.integers(1, 4)), backoff_ticks=1)
    b = CNNBatcher(
        _gen_toy(0), max_batch=int(rng.choice([2, 4])),
        max_wait_ticks=int(rng.integers(0, 3)),
        dispatch_ahead=dispatch_ahead,
        max_inflight=int(rng.integers(1, 4)),
        step_fn=_gen_step(0), device=FaultyDevice(plan))
    reqs = []
    for _ in range(n_ops):
        op = rng.random()
        if op < 0.45:
            rs = [_mk_request(rng, len(reqs) + i, _SHAPES)
                  for i in range(int(rng.integers(1, 4)))]
            b.submit(rs)
            reqs.extend(rs)
        elif op < 0.75:
            b.tick()
        elif op < 0.85:
            b.shed_expired(int(rng.integers(2, 6)))
        elif op < 0.95:
            g = b.generation + 1
            b.swap_apply_fn(_gen_toy(g), step_fn=_gen_step(g))
        else:
            b.drain()
    for _ in range(800):
        if not b.outstanding():
            break
        b.tick()
        if rng.random() < 0.1:  # keep shedding stale work while settling
            b.shed_expired(4)
    b.drain()
    assert not b.outstanding(), f"seed {seed}: requests stuck"
    return b, reqs


def _check_fault_schedule(b, reqs, seed):
    served = shed = 0
    for r in reqs:
        assert r.done, (seed, r.rid)
        if r.error is not None:
            shed += 1
            assert r.out is None, (seed, r.rid)
            assert r.error["code"] in ("deadline", "flush-fault"), r.error
            assert r.error["rid"] == r.rid
        else:
            served += 1
            assert r.generation >= 0, (seed, r.rid)
            want = np.asarray(
                _gen_toy(r.generation)(jnp.asarray(r.x_served)[None]))[0]
            assert np.array_equal(np.asarray(r.out), want), (seed, r.rid)
            assert r.finish_tick >= r.submit_tick >= 0
    st = b.stats
    assert served + shed == len(reqs), seed
    assert st["served"] == served and st["shed"] == shed, seed
    assert st["retries"] <= st["flush_faults"], seed
    assert b._queues == {} and not b._inflight, seed


@pytest.mark.fleet
@pytest.mark.parametrize("dispatch_ahead", [False, True])
def test_fuzz_faults_and_swaps_exactly_once(dispatch_ahead):
    """Seeded fault schedules, both flush modes: exactly-once with
    generation-correct outputs or structured shed errors."""
    for seed in range(30):
        b, reqs = _run_fault_schedule(2000 + seed, dispatch_ahead)
        _check_fault_schedule(b, reqs, 2000 + seed)


@pytest.mark.slow
@pytest.mark.parametrize("dispatch_ahead", [False, True])
def test_fuzz_faults_and_swaps_long(dispatch_ahead):
    """The long sweep (>=100 seeds per mode) for nightly runs."""
    for seed in range(120):
        b, reqs = _run_fault_schedule(5000 + seed, dispatch_ahead,
                                      n_ops=30)
        _check_fault_schedule(b, reqs, 5000 + seed)


def test_fault_shed_after_retry_budget():
    """A bucket that keeps faulting sheds with flush-fault after
    max_retries consecutive failures — it never wedges the scheduler."""
    plan = FaultPlan(seed=0, p_flush_fail=1.0, max_retries=2,
                     backoff_ticks=1)
    b = CNNBatcher(_gen_toy(0), max_batch=2, max_wait_ticks=0,
                   step_fn=_gen_step(0), device=FaultyDevice(plan))
    rs = [CNNRequest(rid=i, x=np.ones((5, 3), np.float32))
          for i in range(2)]
    b.submit(rs)
    for _ in range(20):
        b.tick()
        if all(r.done for r in rs):
            break
    assert all(r.done and r.error["code"] == "flush-fault" for r in rs)
    assert all(r.out is None for r in rs)
    assert b.stats["shed"] == 2
    assert b.stats["flush_faults"] >= 3  # initial + retries
    assert b.drain() == 0


def test_backoff_delays_retry():
    """After a fault, the bucket is not retried until the backoff tick
    passes (attempt-scaled), and a clean device then serves it."""
    class OneShot:
        """Fails the first flush attempt only."""
        def __init__(self):
            self.dev = FaultyDevice(FaultPlan(seed=1, p_flush_fail=1.0))
            self.calls = 0
            self.max_retries = 3
            self.backoff_ticks = 2
        def flush_fate(self, *, tick=-1):
            self.calls += 1
            if self.calls == 1:
                return self.dev.flush_fate(tick=tick)
            from repro.serve.faults import FlushFate
            return FlushFate(False, 0, -1)
    dev = OneShot()
    b = CNNBatcher(_gen_toy(0), max_batch=2, max_wait_ticks=0,
                   step_fn=_gen_step(0), device=dev)
    r = CNNRequest(rid=0, x=np.ones((5, 3), np.float32))
    b.submit([r])
    b.tick()                      # faults; backoff until tick + 2
    assert not r.done and b.stats["retries"] == 1
    b.tick()                      # still backing off: no flush attempt
    assert dev.calls == 1 and not r.done
    b.tick()                      # backoff expired: retries and serves
    assert r.done and r.error is None
    assert np.array_equal(
        np.asarray(r.out),
        np.asarray(_gen_toy(0)(jnp.asarray(r.x_served)[None]))[0])


# -- tick-level golden --------------------------------------------------------
#
# Seeded dispatch-ahead schedules on 1 and 4 lanes, with and without the
# fault layer (failed and stuck flushes), with hot-swaps and deadline
# sheds interleaved. ``fixtures/tick_golden.json`` holds, per request, the
# flush that served it, its lane, ``wait_ticks``, ``finish_tick``,
# ``generation``, shed code and an output digest, and the batcher's stats:
# what the tick schedule decides. It was recorded with the tick order that
# resolved every ready flush before the first pack. Any order inside a
# tick must reproduce it exactly; regenerate it (``python
# tests/test_serving_fuzz.py``) only for a change that means to move
# requests between ticks, flushes or lanes.

import hashlib
import json
import os

from repro.serve.spans import SpanLog

_GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tick_golden.json")
_GOLDEN_CASES = [(1, False), (1, True), (4, False), (4, True)]
_GOLDEN_SEEDS = range(8)
_GOLDEN_SYNC_SEEDS = range(4)  # sync mode, the same schedules


class _ResolvedFlush(SpanLog):
    """Keeps only the flush id of the latest ``serve.resolve`` span: it
    is recorded just before the ``resolve`` event of the same flush."""
    __slots__ = ("flush",)

    def record(self, start_ns, name, **attrs):
        if name == "serve.resolve":
            self.flush = attrs["flush"]


def _run_golden_schedule(seed, n_replicas, faults, *, dispatch_ahead=True,
                         n_ops=36):
    """A seeded schedule that keeps the windows full often: bursts of up
    to six requests over three shapes, ticks, hot-swaps, sheds and the
    odd drain. Returns (batcher, requests, {rid: (flush, lane)})."""
    rng = np.random.default_rng((seed, n_replicas, faults))
    device = None
    if faults:
        device = FaultyDevice(FaultPlan(
            seed=seed, p_flush_fail=0.2, p_stuck=0.3, max_stuck_ticks=2,
            p_canary_corrupt=0.0, max_retries=3, backoff_ticks=1))
    served_by = {}
    log = _ResolvedFlush()

    def on_event(etype, kw):
        if etype == "resolve":
            for r in kw["reqs"]:
                served_by[r.rid] = (log.flush, kw["replica"])
        elif etype == "flush" and dispatch_ahead:
            # a window slot is freed before a flush reuses it
            assert len(b._lanes[kw["replica"]].inflight) < b.max_inflight

    b = CNNBatcher(
        _gen_toy(0), max_batch=int(rng.choice([2, 4])),
        max_wait_ticks=int(rng.integers(0, 3)),
        dispatch_ahead=dispatch_ahead,
        max_inflight=int(rng.integers(1, 4)), step_fn=_gen_step(0),
        device=device, n_replicas=n_replicas, on_event=on_event)
    b.spans = log
    reqs = []
    for _ in range(n_ops):
        op = rng.random()
        if op < 0.5:
            rs = [_mk_request(rng, len(reqs) + i, _SHAPES[:3])
                  for i in range(int(rng.integers(1, 7)))]
            b.submit(rs)
            reqs.extend(rs)
        elif op < 0.88:
            b.tick()
        elif op < 0.93:
            b.shed_expired(int(rng.integers(3, 7)))
        elif op < 0.97:
            g = b.generation + 1
            b.swap_apply_fn(_gen_toy(g), step_fn=_gen_step(g))
        else:
            b.drain()
    for _ in range(800):
        if not b.outstanding():
            break
        b.tick()
    b.drain()
    assert not b.outstanding(), f"seed {seed}: requests stuck"
    return b, reqs, served_by


def _golden_record(seed, n_replicas, faults, dispatch_ahead=True):
    b, reqs, served_by = _run_golden_schedule(
        seed, n_replicas, faults, dispatch_ahead=dispatch_ahead)
    rows = []
    for r in reqs:
        flush, lane = served_by.get(r.rid, (None, None))
        out = None if r.out is None else hashlib.blake2s(
            np.ascontiguousarray(r.out).tobytes(), digest_size=8).hexdigest()
        rows.append([r.rid, flush, lane, r.wait_ticks, r.finish_tick,
                     r.generation, r.error and r.error["code"], out])
    return {"requests": rows, "stats": b.stats}


def _golden_key(seed, n_replicas, faults, dispatch_ahead=True):
    mode = "ahead" if dispatch_ahead else "sync"
    return f"{mode}/lanes{n_replicas}/faults{int(faults)}/seed{seed}"


def _golden_all():
    out = {}
    for n, faults in _GOLDEN_CASES:
        for seed in _GOLDEN_SEEDS:
            out[_golden_key(seed, n, faults)] = _golden_record(
                seed, n, faults)
        for seed in _GOLDEN_SYNC_SEEDS:
            out[_golden_key(seed, n, faults, False)] = _golden_record(
                seed, n, faults, False)
    return out


def _load_golden():
    with open(_GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("n_replicas,faults", _GOLDEN_CASES)
@pytest.mark.parametrize("dispatch_ahead", [True, False])
def test_tick_golden(n_replicas, faults, dispatch_ahead):
    """Every request lands in the same flush, on the same lane, in the
    same ticks and with the same answer as the recorded schedule, and
    the stats match (the deferred-resolve counter is newer than the
    fixture and checked on its own)."""
    golden = _load_golden()
    seeds = _GOLDEN_SEEDS if dispatch_ahead else _GOLDEN_SYNC_SEEDS
    for seed in seeds:
        key = _golden_key(seed, n_replicas, faults, dispatch_ahead)
        got = json.loads(json.dumps(
            _golden_record(seed, n_replicas, faults, dispatch_ahead)))
        want = golden[key]
        assert got["requests"] == want["requests"], key
        deferred = got["stats"].pop("deferred_resolves")
        assert got["stats"] == want["stats"], key
        assert 0 <= deferred <= got["stats"]["flushes"], key
        if not dispatch_ahead:
            assert deferred == 0, key


if __name__ == "__main__":
    os.makedirs(os.path.dirname(_GOLDEN), exist_ok=True)
    with open(_GOLDEN, "w") as f:
        json.dump(_golden_all(), f, separators=(",", ":"))
        f.write("\n")
    print(f"wrote {_GOLDEN}")
