"""Shape-bucketed request batching for integer CNN inference.

CNN serving, unlike LM decode (serve/batching.ContinuousBatcher), is
single-shot: one forward pass per request, no KV state to keep resident.
The production problems are jit's static shapes — every distinct
(batch, spatial) signature compiles a fresh executable — small-batch waste
(B=1 requests leave the MXU grid mostly idle), and host/device
serialization (a blocking ``device_get`` idles the device while the host
unpacks results and packs the next batch).

Shape policy:
  * **Ladder frontend.** With a ``serve.shape_ladder.ShapeLadder``, every
    request is crop/pad-normalized onto a configured rung before
    bucketing, so the jit-signature count is bounded by
    ``len(ladder.shapes) * (log2(max_batch) + 1)`` per payload dtype
    (buckets key on dtype too: int8 code traffic and float traffic on
    the same rung compile separately), regardless of traffic shapes.
    Normalization commutes with the learned quantizer (code 0 == 0.0), so
    it is equally valid on int8 codes — the integer path stays integer.
    A payload matching no rung still serves, raw, under its own bucket
    (counted in ``stats["ladder_misses"]``).
  * **Shape buckets.** Requests group by the exact (served) input shape
    and dtype; an unseen shape compiles its own bucket on first flush.
  * **Batch buckets.** A flush pads the batch dimension with zero rows up
    to the smallest power of two >= the pending count (capped at
    ``max_batch``), so each shape compiles at most log2(max_batch)+1
    executables. Pad-row outputs are discarded.
  * **Donation.** The padded input buffer is donated to the jitted step on
    accelerator backends (skipped on CPU, where jax cannot honor it).

Scheduling model — a ``tick()`` is one host scheduling quantum:
  * **Candidates & priority.** A bucket is a flush candidate when it can
    fill ``max_batch`` or has waited more than ``max_wait_ticks`` ticks.
    Candidates rank by ``(age, fill_ratio)`` descending across buckets —
    a starved odd-shape bucket outranks a perpetually-full hot one once
    its age pulls ahead, so no bucket sits behind dict order forever.
  * **Sync mode** (``dispatch_ahead=False``): ``_flush`` dispatches the
    jitted step and blocks on ``device_get``. The blocking fetch consumes
    the host quantum, so a tick performs at most ONE flush; remaining
    candidates age into the next tick.
  * **Dispatch-ahead** (``dispatch_ahead=True``): ``_flush`` dispatches
    and parks the un-fetched device result on an ``InflightFlush``; the
    host keeps packing. A tick resolves every in-flight result that is
    ready (dispatched on an earlier tick: the device ran during the
    inter-tick interval) and dispatches up to the window slots those
    resolves free. Where a ready result resolves depends on its lane's
    window. With a free slot, at the start of the tick, before any
    pack. In a full window the ready results are *due*: each flush
    routed to the lane first resolves the lane's oldest due result,
    then packs, so the flush queued behind that result keeps the device
    busy while the host packs (resolve k, flush k+2, resolve k+1, flush
    k+3; ``stats["deferred_resolves"]``). Due results no flush displaced
    resolve at the end of the tick. Routing, the budget and
    ``inflight_peak`` count due results as resolved, so every request
    dispatches and completes in the same tick, on the same lane, as if
    all had resolved first. When every window is full, further
    candidates are back-pressured into later ticks
    (``stats["window_waits"]`` counts the TICKS that ended with
    candidates still waiting, not the candidates — a
    ticks-under-pressure metric). Requests complete at *resolve* time,
    one tick after dispatch — the pipeline's latency cost for keeping
    the device fed.
  * ``drain()`` flushes everything and resolves every in-flight result
    immediately (shutdown / end of load).

Replica lanes (the serving mesh, docs/SERVING_MESH.md): ``n_replicas``
generalizes the single implicit backend to N execution lanes, each with
its own bounded in-flight window (``max_inflight`` is PER LANE) and,
optionally, its own pinned device (``replica_devices``, e.g.
``launch.mesh.replica_devices``) and its own apply closure over a
``device_put`` copy of the model (``replica_apply_fns``, e.g. built over
``core.integer_inference.replicate_stack``; without it every lane shares
one jitted step — logical replication, the CPU-simulation mode). The
``(age, fill-ratio)`` ranking picks the bucket; the flush then routes to
the least-loaded lane (fewest in-flight flushes, then fewest lifetime
flushes, then lowest lane id — fully deterministic, so a seeded schedule
replays bit-exactly). Replicas serve the SAME model, so routing may only
change timing, never bytes: outputs are invariant to the replica count
(fuzz-proved in tests/test_serving_fuzz.py). Sync mode still performs
one blocking flush per tick (the host quantum is the bottleneck, not the
device); dispatch-ahead's per-tick budget scales with the free window
slots across lanes — that is the replica-scaling throughput win
``benchmarks/run.py --only serve_mesh`` records. With ``mesh`` (a
``launch.mesh.make_serving_mesh`` serving mesh) the jitted step also
data-parallel-shards each flush batch over the ``replica`` axis through
``models.sharding.serving_constrain`` (big-batch DP sharding; a no-op in
values, a layout hint to XLA).

Observability (``stats``): counters (``flushes``, ``served``,
``padded_rows``, ``ladder_hits``, ``ladder_normalized``,
``ladder_misses``, ``window_waits``, ``inflight_peak``,
``noise_trials`` — flushes dispatched under a noise canary config;
``deferred_resolves`` — due results resolved just before the flush that
took their slot;
``flush_faults``/``retries``/``stuck_flushes``/``shed`` — fault-layer
counters, see below) plus per-bucket
``wait_ticks`` percentiles — ``{bucket: {n, p50, p99, max}}`` where wait
is submit-to-dispatch in ticks — and ``wait_ticks_recent``, the same
percentiles over only the last ``wait_window`` samples per bucket (a
second bounded deque): recent latency, not diluted by a long history
(``FleetRuntime.stats`` exports it per model); ``inflight_age``
(dispatch-to-resolve ticks: n/mean/max, the stuck-result metric); and
``replicas``, a per-lane list of flushes/served/in-flight depth/peak/
stuck/device, plus the ids of the devices its results actually landed on
(``out_devices``). Dead buckets (emptied queues) are garbage-collected
after every tick/drain so bucket state stays bounded under high shape
cardinality; wait histograms are kept (bounded per bucket, capped bucket
count) so end-of-run stats survive the GC.

Wall clock (``time.time_ns``, the clock of a JAX profiler trace's
``profile_start_time``): every request carries ``submit_ns`` (its
``submit`` call) and ``dispatch_ns`` (its flush starts packing);
``wait_ms`` is ``dispatch_ns - submit_ns``, beside ``wait_ticks``. One
clock read per ``submit`` call and per flush. With a
``serve.spans.SpanLog`` set on ``spans`` (between ticks; None detaches
it), each flush also records three host spans, linked by ``flush`` (the
lifetime flush count at dispatch, kept on ``InflightFlush.flush``):

  * ``serve.pack`` (``flush``, ``slots``, ``n``, ``bytes``): the padded
    batch allocated and the rows copied in;
  * ``serve.dispatch`` (``flush``, ``lane``): ``device_put`` on a pinned
    lane and the jitted call, which on an unpinned lane enqueues the
    argument's transfer (its host-side layout transpose runs on a
    runtime thread, outside every span);
  * ``serve.resolve`` (``flush``, ``lane``, ``age_ticks``): the host
    blocked in ``device_get`` and the rows unpacked.

With ``spans`` None a span site costs one attribute check. The log has
no bound: attach it for a measured window and detach it after. Spans
never go through ``on_event``, whose payloads replay bit for bit.

Fault boundary (``device``, serve/faults.py): when a device boundary is
installed, every flush dispatch first asks it for a fate. A failed
dispatch never reaches the jitted step — the batch requeues at the
FRONT of its bucket (order preserved), the bucket backs off
``max(1, backoff_ticks * attempt)`` ticks, and after ``max_retries``
consecutive failures the batch is shed with a structured
``flush-fault`` error instead of stalling the scheduler. A "stuck"
fate parks the dispatch-ahead result for extra ticks
(``InflightFlush.ready_tick``) — bounded head-of-line latency the
``inflight_age`` stats expose. ``shed_expired(max_age)`` sheds queued
requests past a deadline with a structured ``deadline`` error
(``CNNRequest.error``; ``done`` is set so accounting stays
exactly-once). Every request carries the ``generation`` of the model
that served it (``swap_apply_fn`` bumps it), stamped at dispatch time —
in-flight results keep the OLD generation across a swap. ``on_event``
receives every decision (flush/fault/retry/shed/resolve/swap) for the
fleet trace; flush/resolve/swap events are tagged with the replica id.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core.noise import NoiseConfig
from ..kernels import fq_conv
from ..models import sharding
from .shape_ladder import ShapeLadder
from .spans import SpanLog


@dataclasses.dataclass
class CNNRequest:
    rid: int
    x: np.ndarray                    # one sample, no batch dim
    out: Optional[np.ndarray] = None
    done: bool = False
    # set by the batcher:
    x_served: Optional[np.ndarray] = None  # ladder-normalized payload
    submit_tick: int = -1
    wait_ticks: int = -1                   # submit -> dispatch, in ticks
    finish_tick: int = -1                  # resolve/shed tick
    generation: int = -1                   # model generation that served it
    error: Optional[Dict] = None           # structured shed error, else None
    # wall clock (time.time_ns) beside the tick fields:
    submit_ns: int = -1
    dispatch_ns: int = -1                  # its flush started packing

    @property
    def wait_ms(self) -> float:
        """Submit to dispatch on the wall clock; -1.0 until dispatched."""
        if self.dispatch_ns < 0:
            return -1.0
        return (self.dispatch_ns - self.submit_ns) / 1e6


@dataclasses.dataclass
class InflightFlush:
    """A dispatched-but-unfetched flush parked on a lane's window."""
    key: Tuple
    reqs: List[CNNRequest]
    dev_out: object                  # un-fetched device result
    dispatch_tick: int
    generation: int = 0              # model generation at dispatch
    ready_tick: int = 0              # dispatch_tick + 1 + injected stuck ticks
    replica: int = 0                 # lane that dispatched it
    flush: int = -1                  # lifetime flush count at dispatch


@dataclasses.dataclass
class ReplicaLane:
    """One replica execution lane: a (possibly shared) jitted step, an
    optional pinned device, and a bounded in-flight window."""
    rid: int
    step: Callable
    device: object = None
    inflight: Deque[InflightFlush] = dataclasses.field(default_factory=deque)
    flushes: int = 0                 # successful dispatches, lifetime
    served: int = 0
    stuck: int = 0
    inflight_peak: int = 0
    # ready flushes left in a full window at the start of a tick: each
    # resolves just before the flush that takes its slot, or at the end
    # of the tick; depth counts them as resolved already
    due: int = 0
    # ids of the devices the lane's results actually landed on
    out_devices: set = dataclasses.field(default_factory=set)


def batch_bucket(n: int, max_batch: int) -> int:
    """Smallest power-of-two slot count that fits n, capped at max_batch."""
    b = 1
    while b < n and b < max_batch:
        b *= 2
    return min(b, max_batch)


_WAIT_HIST_LEN = 4096    # lifetime wait samples kept per bucket
_WAIT_HIST_BUCKETS = 128  # distinct buckets tracked; overflow aggregates


class CNNBatcher:
    """Single-host reference implementation (CPU-testable).

    ``apply_fn`` maps a batched input array to batched outputs (e.g. the
    closure from ``models.kws.int_serve_fn`` / ``models.darknet
    .int_serve_fn``); it is jitted once with the input buffer donated
    off-CPU. ``step_fn`` lets callers share one pre-jitted step across
    batcher instances (the fuzz harness does, to share the compile cache);
    it must be jit-compatible with ``apply_fn``'s semantics.

    **Replica lanes.** ``n_replicas`` lanes share ``apply_fn``'s jitted
    step unless ``replica_apply_fns`` supplies one closure per lane (over
    ``replicate_stack`` device copies); ``replica_devices`` pins each
    lane's dispatch to a device by committing its inputs there. See the
    module docstring for routing and the bit-exactness contract.

    **Noise canary tier.** ``noise_config`` (a ``core.noise.NoiseConfig``
    with any non-zero sigma) makes every flush run noise-perturbed
    integer inference — the paper's §4.4 analog-noise model — with a
    fresh PRNG key per flush (folded from ``noise_seed`` and the trial
    counter, so a canary run is reproducible end-to-end). ``apply_fn``
    must then accept ``(x, noise=..., rng=...)`` — the ``int_serve_fn``
    closures do; if ``step_fn`` is supplied it must accept ``(x, key)``.
    ``stats["noise_trials"]`` counts the noisy flushes dispatched. A
    ``None`` or all-zero config leaves the batcher on the byte-identical
    clean path. (The per-flush trial index depends on how many flushes
    preceded it, so noisy-tier outputs — unlike clean ones — are NOT
    replica-count-invariant; they replay bit-exactly at a fixed count.)

    **Model hot-swap.** ``swap_apply_fn`` replaces the served model
    between flushes — e.g. a freshly rederived ``ConvertedStack`` coming
    out of a deployment-in-the-loop retraining cycle — without dropping
    queued requests or in-flight results; with replica lanes the new
    step installs lane by lane, each install emitting a replica-tagged
    ``swap`` event.
    """

    def __init__(self, apply_fn: Callable, *, max_batch: int = 8,
                 max_wait_ticks: int = 2,
                 ladder: Optional[ShapeLadder] = None,
                 dispatch_ahead: bool = False, max_inflight: int = 2,
                 step_fn: Optional[Callable] = None,
                 noise_config: Optional[NoiseConfig] = None,
                 noise_seed: int = 0,
                 device=None,
                 on_event: Optional[Callable[[str, Dict], None]] = None,
                 n_replicas: int = 1,
                 replica_apply_fns: Optional[Sequence[Callable]] = None,
                 replica_devices: Optional[Sequence] = None,
                 mesh=None,
                 wait_window: int = 256):
        assert max_batch >= 1 and max_inflight >= 1
        assert n_replicas >= 1 and wait_window >= 1
        if step_fn is not None and replica_apply_fns is not None:
            raise ValueError("step_fn and replica_apply_fns are mutually "
                             "exclusive — a shared step IS one closure")
        self.apply_fn = apply_fn
        self.max_batch = max_batch
        self.max_wait_ticks = max_wait_ticks
        self.ladder = ladder
        self.dispatch_ahead = dispatch_ahead
        self.max_inflight = max_inflight         # PER replica lane
        self.wait_window = wait_window
        self.noise_config = noise_config
        self._noisy = noise_config is not None and noise_config.enabled
        self._noise_key = jax.random.key(noise_seed) if self._noisy else None
        self._mesh = mesh
        self._device = device          # serve.faults boundary (or None)
        self._on_event = on_event
        self.spans: Optional[SpanLog] = None  # host span log, or no spans
        self.generation = 0            # bumped by every swap_apply_fn
        self._queues: Dict[Tuple, List[CNNRequest]] = {}
        self._age: Dict[Tuple, int] = {}
        self._backoff: Dict[Tuple, int] = {}        # bucket -> eligible tick
        self._flush_attempts: Dict[Tuple, int] = {}  # consecutive faults
        self._tick_no = 0
        self._replica_apply_fns = list(replica_apply_fns) \
            if replica_apply_fns is not None else None
        if self._replica_apply_fns is not None \
                and len(self._replica_apply_fns) != n_replicas:
            raise ValueError(f"replica_apply_fns has "
                             f"{len(self._replica_apply_fns)} entries for "
                             f"{n_replicas} replicas")
        devs = list(replica_devices) if replica_devices is not None \
            else [None] * n_replicas
        if len(devs) != n_replicas:
            raise ValueError(f"replica_devices has {len(devs)} entries for "
                             f"{n_replicas} replicas")
        if self._replica_apply_fns is None:
            shared = step_fn if step_fn is not None \
                else self._make_step(apply_fn)
            self._lanes = [ReplicaLane(rid=i, step=shared, device=devs[i])
                           for i in range(n_replicas)]
        else:
            self._lanes = [
                ReplicaLane(rid=i, step=self._make_step(fn), device=devs[i])
                for i, fn in enumerate(self._replica_apply_fns)]
        self._signatures: set = set()
        self._wait_hist: Dict[str, Deque[int]] = {}
        self._wait_recent: Dict[str, Deque[int]] = {}
        self._wait_stats_cache: Dict[bool, Optional[Dict]] = {
            False: None, True: None}
        self._inflight_age_sum = 0
        self._inflight_age_n = 0
        self._counters = {
            "flushes": 0, "served": 0, "padded_rows": 0,
            "ladder_hits": 0, "ladder_normalized": 0, "ladder_misses": 0,
            "window_waits": 0, "inflight_peak": 0, "noise_trials": 0,
            "flush_faults": 0, "retries": 0, "stuck_flushes": 0, "shed": 0,
            "inflight_age_max": 0, "deferred_resolves": 0,
        }

    def _emit(self, etype: str, **kw):
        if self._on_event is not None:
            self._on_event(etype, kw)

    def _make_step(self, apply_fn):
        donate = (0,) if jax.default_backend() != "cpu" else ()
        mesh = self._mesh
        if mesh is not None:
            # big-batch DP: shard the flush batch over the serving mesh's
            # replica axis through the shared constrain() path
            if self._noisy:
                nc = self.noise_config
                return jax.jit(
                    lambda x, key: apply_fn(
                        sharding.serving_constrain(x, mesh),
                        noise=nc, rng=key),
                    donate_argnums=donate)
            return jax.jit(
                lambda x: apply_fn(sharding.serving_constrain(x, mesh)),
                donate_argnums=donate)
        if self._noisy:
            nc = self.noise_config
            return jax.jit(lambda x, key: apply_fn(x, noise=nc, rng=key),
                           donate_argnums=donate)
        return jax.jit(apply_fn, donate_argnums=donate)

    def swap_apply_fn(self, apply_fn, *, step_fn=None,
                      replica_apply_fns=None):
        """Hot-swap the served model between flushes.

        The round-trip pipeline's serving edge: after a deploy-QAT
        finetune, ``ConvertedStack.rederive`` (or ``convert_int``) yields
        a fresh stack whose ``int_serve_fn`` closure swaps in here without
        restarting the batcher. Queued-but-undispatched requests serve
        under the NEW model on their next flush; results already in a
        dispatch-ahead window were computed under the old one and resolve
        normally. Per-bucket compiled executables for the new closure
        compile lazily on first flush; ``n_signatures`` keeps counting
        distinct (shape, slots) keys, not recompiles.

        Each swap bumps ``generation`` ONCE, then installs the new step
        replica by replica (``replica_apply_fns`` gives each lane its own
        closure over a freshly placed stack copy; otherwise every lane
        shares one step). Each lane install emits a ``swap`` event tagged
        with the replica id — the fleet trace records the rollout, not
        just the decision. Requests record the generation that computed
        them (stamped at dispatch), so traces and tests can attribute
        every output to a serving model generation.
        """
        if step_fn is not None and replica_apply_fns is not None:
            raise ValueError("step_fn and replica_apply_fns are mutually "
                             "exclusive")
        if replica_apply_fns is not None \
                and len(replica_apply_fns) != len(self._lanes):
            raise ValueError(f"replica_apply_fns has "
                             f"{len(replica_apply_fns)} entries for "
                             f"{len(self._lanes)} replicas")
        self.apply_fn = apply_fn
        self._replica_apply_fns = list(replica_apply_fns) \
            if replica_apply_fns is not None else None
        self.generation += 1
        shared = None
        if self._replica_apply_fns is None:
            shared = step_fn if step_fn is not None \
                else self._make_step(apply_fn)
        for lane in self._lanes:
            lane.step = shared if shared is not None \
                else self._make_step(self._replica_apply_fns[lane.rid])
            self._emit("swap", generation=self.generation,
                       tick=self._tick_no, replica=lane.rid)

    # -- request intake -----------------------------------------------------

    def submit(self, reqs: List[CNNRequest]):
        prepared, seen = [], set()  # validate + normalize the WHOLE list
        for r in reqs:  # before any mutation: a mid-list failure
            # (resubmission, duplicate, malformed payload) must never
            # partially enqueue the call
            if id(r) in seen or r.x_served is not None or r.done:
                raise ValueError(f"request {r.rid} was already submitted")
            seen.add(id(r))
            x = np.asarray(r.x)
            xn = self.ladder.normalize(x) if self.ladder is not None else x
            prepared.append((r, x, xn))
        now = time.time_ns()
        for r, x, xn in prepared:
            if self.ladder is not None:
                if xn is None:
                    self._counters["ladder_misses"] += 1
                else:
                    self._counters["ladder_hits"] += 1
                    if xn.shape != x.shape:
                        self._counters["ladder_normalized"] += 1
                    x = xn
            r.x_served = x
            r.submit_tick = self._tick_no
            r.submit_ns = now
            key = (x.shape, x.dtype.str)
            self._queues.setdefault(key, []).append(r)
            self._age.setdefault(key, 0)

    def pending(self) -> int:
        """Requests queued but not yet dispatched."""
        return sum(len(q) for q in self._queues.values())

    @property
    def _inflight(self) -> List[InflightFlush]:
        """All in-flight flushes across lanes, oldest dispatch first (a
        read-only merged view; single-replica tests index it directly —
        mutations must go through the lanes)."""
        out = [f for lane in self._lanes for f in lane.inflight]
        out.sort(key=lambda f: (f.dispatch_tick, f.replica))
        return out

    @property
    def in_flight(self) -> int:
        """Requests dispatched but not yet resolved (dispatch-ahead only)."""
        return sum(len(f.reqs) for lane in self._lanes
                   for f in lane.inflight)

    @staticmethod
    def _depth(lane: ReplicaLane) -> int:
        """In-flight flushes on the lane, its due ready ones not counted."""
        return len(lane.inflight) - lane.due

    def _inflight_flushes(self) -> int:
        return sum(self._depth(lane) for lane in self._lanes)

    def _free_window(self) -> int:
        return sum(max(0, self.max_inflight - self._depth(lane))
                   for lane in self._lanes)

    def outstanding(self) -> int:
        return self.pending() + self.in_flight

    # -- flushing -----------------------------------------------------------

    def _route(self) -> ReplicaLane:
        """Least-loaded replica lane, deterministically: min in-flight
        depth (due ready flushes not counted), then fewest lifetime
        flushes (round-robin under sync mode's always-empty windows),
        then lowest lane id."""
        return min(self._lanes,
                   key=lambda l: (self._depth(l), l.flushes, l.rid))

    def _dispatch(self, lane: ReplicaLane, flush: int, *args):
        """Run the lane's jitted step on the lane's device and inside the
        kernels' autotune replica scope (table misses recorded at trace
        time attribute to the lane that compiled them). The inputs are
        committed to the lane's device, so the computation runs there: a
        ``jax.default_device`` scope alone would not move a step whose
        closure holds arrays committed elsewhere."""
        spans = self.spans
        t = time.time_ns() if spans is not None else 0
        if lane.device is not None:
            args = jax.device_put(args, lane.device)
        with fq_conv.replica_scope(lane.rid):
            out = lane.step(*args)
        if spans is not None:
            spans.record(t, "serve.dispatch", flush=flush, lane=lane.rid)
        return out

    def _flush(self, key: Tuple, reqs: List[CNNRequest]) -> int:
        """Dispatch one padded batch to the least-loaded lane. Returns
        #requests COMPLETED now (sync: all of them; dispatch-ahead: those
        of the due flush it displaced from a full window, if any; its own
        resolve later).

        With a fault boundary installed the dispatch can fail BEFORE
        reaching the device: the batch requeues at the front of its
        bucket under backoff, or — past the bounded retry budget — sheds
        with a structured error."""
        shape, dtype = key
        stuck = 0
        if self._device is not None:
            fate = self._device.flush_fate(tick=self._tick_no)
            if fate.fail:
                return self._flush_fault(key, reqs)
            stuck = fate.stuck_ticks if self.dispatch_ahead else 0
        lane = self._route()
        done = 0
        if len(lane.inflight) >= self.max_inflight:
            # full window: the slot's due flush resolves now, while the
            # flush behind it keeps the device busy through the pack
            lane.due -= 1
            self._counters["deferred_resolves"] += 1
            done = self._resolve_lane(lane)
        slots = batch_bucket(len(reqs), self.max_batch)
        fid = self._counters["flushes"]
        now = time.time_ns()
        x = np.zeros((slots,) + shape, dtype=np.dtype(dtype))
        for i, r in enumerate(reqs):
            x[i] = r.x_served
            r.wait_ticks = self._tick_no - r.submit_tick
            r.dispatch_ns = now
            r.generation = self.generation
        spans = self.spans
        if spans is not None:
            spans.record(now, "serve.pack", flush=fid, slots=slots,
                         n=len(reqs), bytes=x.nbytes)
        self._record_waits(key, reqs)
        self._signatures.add((key, slots))
        self._counters["flushes"] += 1
        self._counters["padded_rows"] += slots - len(reqs)
        lane.flushes += 1
        self._age[key] = 0  # every flush restarts the bucket's wait clock
        self._flush_attempts.pop(key, None)  # success resets retry budget
        if self._noisy:
            # one fresh key per flush: noisy trials differ flush-to-flush
            # but the whole canary run replays bit-exact from noise_seed
            key_n = jax.random.fold_in(self._noise_key,
                                       self._counters["noise_trials"])
            self._counters["noise_trials"] += 1
            dev = self._dispatch(lane, fid, x, key_n)
        else:
            dev = self._dispatch(lane, fid, x)
        lane.out_devices.update(
            d.id for leaf in jax.tree_util.tree_leaves(dev)
            if isinstance(leaf, jax.Array) for d in leaf.devices())
        self._emit("flush", key=key, tick=self._tick_no, n=len(reqs),
                   slots=slots, generation=self.generation, stuck=stuck,
                   replica=lane.rid)
        if self.dispatch_ahead:
            if stuck:
                self._counters["stuck_flushes"] += 1
                lane.stuck += 1
            lane.inflight.append(
                InflightFlush(key, reqs, dev, self._tick_no,
                              generation=self.generation,
                              ready_tick=self._tick_no + 1 + stuck,
                              replica=lane.rid, flush=fid))
            lane.inflight_peak = max(lane.inflight_peak, self._depth(lane))
            self._counters["inflight_peak"] = max(
                self._counters["inflight_peak"], self._inflight_flushes())
            return done
        n = self._finish(reqs, dev, fid, lane.rid, 0)
        lane.served += n
        self._emit("resolve", key=key, tick=self._tick_no, reqs=reqs,
                   generation=self.generation, age=0, replica=lane.rid)
        return n

    def _flush_fault(self, key: Tuple, reqs: List[CNNRequest]) -> int:
        """A dispatch the fault layer failed: bounded retry w/ backoff,
        then shed. The step never ran, so requeueing is lossless."""
        attempt = self._flush_attempts.get(key, 0) + 1
        self._flush_attempts[key] = attempt
        self._counters["flush_faults"] += 1
        self._emit("fault", kind="flush-fail", key=key, tick=self._tick_no,
                   attempt=attempt)
        if attempt > self._device.max_retries:
            self._flush_attempts.pop(key, None)
            self._backoff.pop(key, None)
            self._shed(reqs, code="flush-fault", attempts=attempt)
            return 0
        self._queues.setdefault(key, [])[:0] = reqs  # front: order kept
        self._age.setdefault(key, 0)
        until = self._tick_no + max(1, self._device.backoff_ticks * attempt)
        self._backoff[key] = until
        self._counters["retries"] += 1
        self._emit("retry", key=key, tick=self._tick_no, attempt=attempt,
                   backoff_until=until)
        return 0

    def _shed(self, reqs: List[CNNRequest], *, code: str, **details):
        """Shed requests with a structured error (exactly-once: ``done``
        is set, so a later serve attempt would raise double-served)."""
        for r in reqs:
            if r.done:
                raise RuntimeError(f"request {r.rid} double-served (shed)")
            r.error = {"code": code, "rid": r.rid, "tick": self._tick_no,
                       "submit_tick": r.submit_tick, **details}
            r.finish_tick = self._tick_no
            r.done = True
            self._counters["shed"] += 1
            self._emit("shed", rid=r.rid, code=code, tick=self._tick_no,
                       submit_tick=r.submit_tick, **details)

    def shed_expired(self, max_age_ticks: int) -> List[CNNRequest]:
        """Shed queued requests older than ``max_age_ticks`` (submit ->
        now) with a structured ``deadline`` error, instead of letting
        them stall behind backoff or a full window. Returns the shed
        requests; in-flight results are never shed (they resolve)."""
        out = []
        for key, q in self._queues.items():
            keep = []
            for r in q:
                age = self._tick_no - r.submit_tick
                if age > max_age_ticks:
                    out.append(r)
                else:
                    keep.append(r)
            self._queues[key] = keep
        self._shed(out, code="deadline", deadline_ticks=max_age_ticks)
        return out

    def _finish(self, reqs: List[CNNRequest], dev, flush: int, lane: int,
                age: int) -> int:
        spans = self.spans
        t = time.time_ns() if spans is not None else 0
        y = np.asarray(jax.device_get(dev))
        for i, r in enumerate(reqs):
            if r.done:
                raise RuntimeError(f"request {r.rid} double-served")
            r.out = y[i]
            r.finish_tick = self._tick_no
            r.done = True
        self._counters["served"] += len(reqs)
        if spans is not None:
            spans.record(t, "serve.resolve", flush=flush, lane=lane,
                         age_ticks=age)
        return len(reqs)

    def _resolve_lane(self, lane: ReplicaLane) -> int:
        """Pop + fetch the lane's head flush, recording its window age."""
        f = lane.inflight.popleft()
        age = self._tick_no - f.dispatch_tick
        self._counters["inflight_age_max"] = max(
            self._counters["inflight_age_max"], age)
        self._inflight_age_sum += age
        self._inflight_age_n += 1
        n = self._finish(f.reqs, f.dev_out, f.flush, f.replica, age)
        lane.served += n
        self._emit("resolve", key=f.key, tick=self._tick_no, reqs=f.reqs,
                   generation=f.generation, age=age, replica=f.replica)
        return n

    def _resolve_one(self) -> int:
        """Fetch the globally-oldest in-flight head, ready or not (drain
        / window back-pressure: the host blocks on it anyway)."""
        lane = min((l for l in self._lanes if l.inflight),
                   key=lambda l: (l.inflight[0].dispatch_tick, l.rid))
        return self._resolve_lane(lane)

    def _ready_heads(self, lane: ReplicaLane) -> int:
        """Leading in-flight flushes of the lane ready by this tick."""
        n = 0
        for f in lane.inflight:
            if f.ready_tick > self._tick_no:
                break
            n += 1
        return n

    def _resolve_older_than(self, tick: int) -> int:
        """Fetch in-flight results that are ready by ``tick`` (the device
        had the inter-tick interval to run them; a stuck result's
        ``ready_tick`` was pushed out by the fault layer), except on lanes
        with due flushes. Lanes merge in (ready_tick, dispatch_tick, lane
        id) order — deterministic."""
        n = 0
        while True:
            best = None
            for lane in self._lanes:
                if lane.inflight and not lane.due \
                        and lane.inflight[0].ready_tick <= tick:
                    rank = (lane.inflight[0].ready_tick,
                            lane.inflight[0].dispatch_tick, lane.rid)
                    if best is None or rank < best[0]:
                        best = (rank, lane)
            if best is None:
                return n
            n += self._resolve_lane(best[1])

    def _candidate(self) -> Optional[Tuple]:
        """Highest-priority flush candidate by (age, fill-ratio), or None."""
        best, best_rank = None, None
        for key, q in self._queues.items():
            if not q:
                continue
            if self._backoff.get(key, 0) > self._tick_no:
                continue  # faulted bucket still backing off
            fill = len(q) / self.max_batch
            if fill < 1.0 and self._age[key] <= self.max_wait_ticks:
                continue
            rank = (self._age[key], fill)
            if best is None or rank > best_rank:
                best, best_rank = key, rank
        return best

    def _gc_buckets(self):
        """Drop empty bucket state so high shape cardinality stays bounded."""
        for key in [k for k, q in self._queues.items() if not q]:
            del self._queues[key]
            self._age.pop(key, None)
            self._backoff.pop(key, None)
            self._flush_attempts.pop(key, None)
        for key in [k for k, t in self._backoff.items()
                    if t <= self._tick_no]:
            del self._backoff[key]  # expired backoff, state stays bounded

    def tick(self) -> int:
        """One host scheduling quantum. Returns #requests completed.

        Resolve earlier-tick in-flight results, age the buckets, then
        flush the ranked candidates within this tick's budget: one
        blocking flush (sync — the blocking fetch eats the quantum no
        matter how many lanes exist) or the free in-flight window slots
        summed across every replica lane, counting each ready flush as a
        free slot (dispatch-ahead — the budget that scales with the
        replica count).

        Dispatch-ahead resolves a ready flush at one of three points of
        the tick, always in the tick it is ready. On a lane with a free
        window slot, before any pack. On a full lane, the ready flushes
        are due: each flush routed there first resolves the lane's
        oldest, so the flush queued behind it keeps the device busy
        while the host packs (resolve k, flush k+2, resolve k+1, flush
        k+3; ``stats["deferred_resolves"]``). Due flushes no flush
        displaced resolve at the end of the tick."""
        served = 0
        if self.dispatch_ahead:
            for lane in self._lanes:
                lane.due = self._ready_heads(lane) \
                    if len(lane.inflight) >= self.max_inflight else 0
            served += self._resolve_older_than(self._tick_no)
            budget = self._free_window()
        else:
            budget = 1
        for key, q in self._queues.items():
            if q:
                self._age[key] += 1
        while budget > 0:
            key = self._candidate()
            if key is None:
                break
            q = self._queues[key]
            take = min(len(q), self.max_batch)
            self._queues[key] = q[take:]
            served += self._flush(key, q[:take])
            budget -= 1
        if self.dispatch_ahead:
            for lane in self._lanes:
                lane.due = 0
            served += self._resolve_older_than(self._tick_no)
            if self._candidate() is not None:
                # a tick that ended with candidates still back-pressured
                # behind the full window(s) (ticks-under-pressure, not a
                # per-candidate count)
                self._counters["window_waits"] += 1
        self._gc_buckets()
        self._tick_no += 1
        return served

    def drain(self) -> int:
        """Flush every pending request and resolve every in-flight result
        now (shutdown / end of load). Returns #requests completed.

        Dispatch faults during drain retry immediately (no ticks are
        advancing to serve a backoff): a faulted batch lands back in its
        queue and the outer loop re-attempts it until it dispatches or
        exhausts the retry budget and sheds — drain terminates either
        way, with every request completed exactly once."""
        served = 0
        while True:
            keys = [k for k, q in self._queues.items() if q]
            if not keys:
                break
            for key in keys:
                q, self._queues[key] = self._queues[key], []
                while q:
                    batch, q = q[:self.max_batch], q[self.max_batch:]
                    if self.dispatch_ahead and self._free_window() == 0:
                        served += self._resolve_one()  # window back-pressure
                    served += self._flush(key, batch)
        while any(lane.inflight for lane in self._lanes):
            served += self._resolve_one()
        self._gc_buckets()
        return served

    @property
    def n_signatures(self) -> int:
        """Distinct (shape, slots) jit signatures compiled so far."""
        return len(self._signatures)

    # -- observability ------------------------------------------------------

    def _record_waits(self, key: Tuple, reqs: List[CNNRequest]):
        label = f"{key[0]}/{np.dtype(key[1]).name}"
        if label not in self._wait_hist and \
                len(self._wait_hist) >= _WAIT_HIST_BUCKETS:
            label = "<overflow>"
        hist = self._wait_hist.setdefault(label, deque(maxlen=_WAIT_HIST_LEN))
        recent = self._wait_recent.setdefault(
            label, deque(maxlen=self.wait_window))
        waits = [r.wait_ticks for r in reqs]
        hist.extend(waits)
        recent.extend(waits)
        self._wait_stats_cache = {False: None, True: None}

    def wait_stats(self, *, window: bool = False
                   ) -> Dict[str, Dict[str, float]]:
        """Per-bucket submit-to-dispatch wait percentiles, in ticks.

        ``window=True`` computes them over only the last ``wait_window``
        samples per bucket (a second bounded deque) — the fleet-SLO view:
        lifetime percentiles dilute a latency regression under hours of
        healthy history, the windowed ones surface it within one window.

        Cached between flushes so polling ``stats`` for a counter never
        pays a percentile pass over the histograms."""
        if self._wait_stats_cache[window] is None:
            src = self._wait_recent if window else self._wait_hist
            out = {}
            for label, hist in src.items():
                a = np.asarray(hist)
                out[label] = {
                    "n": int(a.size),
                    "p50": float(np.percentile(a, 50)),
                    "p99": float(np.percentile(a, 99)),
                    "max": int(a.max()),
                }
            self._wait_stats_cache[window] = out
        return self._wait_stats_cache[window]

    @property
    def stats(self) -> Dict:
        d = dict(self._counters)
        d["generation"] = self.generation
        d["wait_ticks"] = self.wait_stats()
        d["wait_ticks_recent"] = self.wait_stats(window=True)
        d["inflight_age"] = {
            "n": self._inflight_age_n,
            "mean": (self._inflight_age_sum / self._inflight_age_n
                     if self._inflight_age_n else 0.0),
            "max": self._counters["inflight_age_max"],
        }
        d["n_replicas"] = len(self._lanes)
        d["replicas"] = [
            {"replica": lane.rid, "flushes": lane.flushes,
             "served": lane.served, "inflight": len(lane.inflight),
             "inflight_peak": lane.inflight_peak, "stuck": lane.stuck,
             "device": str(lane.device) if lane.device is not None
             else None, "out_devices": sorted(lane.out_devices)}
            for lane in self._lanes]
        return d

    # -- convenience --------------------------------------------------------

    def run(self, reqs: List[CNNRequest], max_ticks: int = 10_000
            ) -> Dict[int, np.ndarray]:
        """Serve a request list to completion; returns rid -> output."""
        self.submit(reqs)
        for _ in range(max_ticks):
            if self.pending() == 0 and \
                    not any(lane.inflight for lane in self._lanes):
                break
            self.tick()
        self.drain()
        return {r.rid: r.out for r in reqs}
