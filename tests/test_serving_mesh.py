"""Serving-mesh replica lanes (ISSUE 10): placement helpers, load-aware
routing, per-lane windows/stats/swap events, per-replica autotune miss
attribution, and a forced-multi-device subprocess run.

Everything in-process runs on the 1-device CPU host in oversubscribed
simulation mode (``launch.mesh.replica_devices`` maps every lane to the
same device — lanes stay logically distinct). The subprocess test forces
``--xla_force_host_platform_device_count=4`` and runs the real thing:
a 4-replica serving mesh, ``replicate_stack`` placement onto four
distinct devices, per-replica apply closures, and a mesh-sharded flush.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.report import Report
from repro.core import integer_inference as ii
from repro.kernels import fq_conv
from repro.launch import mesh as mesh_mod
from repro.models import sharding
from repro.serve.cnn_batching import CNNBatcher, CNNRequest

pytestmark = pytest.mark.mesh


def _toy(x):
    xi = jnp.round(x.astype(jnp.float32) * 8.0).astype(jnp.int32)
    axes = tuple(range(1, x.ndim))
    return jnp.sum(xi * xi, axis=axes) * 3 + jnp.max(xi, axis=axes)


_STEP = jax.jit(_toy)


def _reqs(shape, n, *, rid0=0, seed=0):
    rng = np.random.default_rng((seed, rid0))
    return [CNNRequest(rid=rid0 + i,
                       x=rng.standard_normal(shape).astype(np.float32))
            for i in range(n)]


# -- placement helpers -------------------------------------------------------


def test_replica_devices_oversubscribes_round_robin():
    devs = mesh_mod.replica_devices(4)
    assert len(devs) == 4
    host = jax.devices()
    for i, d in enumerate(devs):
        assert d == host[i % len(host)]


def test_make_serving_mesh_raises_when_devices_short():
    n = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="devices"):
        mesh_mod.make_serving_mesh(n)


def test_serving_constrain_is_value_noop():
    mesh = mesh_mod.make_serving_mesh(1)
    x = jnp.arange(24.0).reshape(4, 6)
    y = jax.jit(lambda t: sharding.serving_constrain(t, mesh))(x)
    assert np.array_equal(np.asarray(y), np.asarray(x))


def test_place_stack_digest_invariant():
    from conftest import trained_int_params
    from repro.core.quant import QuantConfig
    from repro.models import kws
    cfg = kws.KWSConfig.reduced()
    qcfg = QuantConfig(2, 4, 4, fq=True)
    _, _, ip = trained_int_params(
        kws, cfg, [f"conv{i}" for i in range(len(cfg.dilations))], qcfg)
    placed = ii.place_stack(ip, jax.devices()[0])
    assert ii.stack_digest(placed) == ii.stack_digest(ip)
    copies = ii.replicate_stack(ip, mesh_mod.replica_devices(3))
    assert len(copies) == 3
    assert all(ii.stack_digest(c) == ii.stack_digest(ip) for c in copies)


# -- replica-lane routing ----------------------------------------------------


def test_dispatch_ahead_budget_scales_with_lanes():
    """Two full buckets in one tick: one lane serves each with 2 replicas
    (no window wait); a single replica's window of 1 back-pressures the
    second bucket into the next tick."""
    def run(n):
        b = CNNBatcher(_toy, max_batch=4, max_wait_ticks=2,
                       dispatch_ahead=True, max_inflight=1, step_fn=_STEP,
                       n_replicas=n,
                       replica_devices=mesh_mod.replica_devices(n)
                       if n > 1 else None)
        b.submit(_reqs((5, 3), 4, rid0=0))
        b.submit(_reqs((4, 4), 4, rid0=4))
        b.tick()
        return b
    b2 = run(2)
    st = b2.stats
    assert st["flushes"] == 2 and st["window_waits"] == 0
    assert [l["flushes"] for l in st["replicas"]] == [1, 1]
    assert [l["inflight"] for l in st["replicas"]] == [1, 1]
    b1 = run(1)
    st1 = b1.stats
    assert st1["flushes"] == 1 and st1["window_waits"] == 1
    for b in (b1, b2):  # both settle to the same served set
        b.drain()
        assert b.stats["served"] == 8


def test_routing_is_least_loaded_then_deterministic():
    b = CNNBatcher(_toy, max_batch=2, dispatch_ahead=True, max_inflight=2,
                   step_fn=_STEP, n_replicas=3)
    # four full buckets flushed within one tick: lanes 0,1,2 then the
    # least-loaded tie broken by lifetime flushes -> lane 0 again
    for i, shape in enumerate([(5, 3), (4, 4), (7, 2), (6,)]):
        b.submit(_reqs(shape, 2, rid0=2 * i))
    b.tick()
    assert [l["flushes"] for l in b.stats["replicas"]] == [2, 1, 1]
    b.drain()
    assert b.stats["served"] == 8


def test_replica_scaling_fewer_ticks():
    """Same seeded burst, dispatch-ahead: 4 lanes settle in strictly
    fewer ticks than 1 lane (the benchmark's scaling claim, in miniature)."""
    def ticks(n):
        b = CNNBatcher(_toy, max_batch=4, max_wait_ticks=2,
                       dispatch_ahead=True, max_inflight=1, step_fn=_STEP,
                       n_replicas=n,
                       replica_devices=mesh_mod.replica_devices(n))
        for i, shape in enumerate([(5, 3), (4, 4), (7, 2), (3, 3, 2)]):
            b.submit(_reqs(shape, 4, rid0=4 * i, seed=n))
        t = 0
        while b.outstanding() and t < 100:
            b.tick()
            t += 1
        assert b.stats["served"] == 16
        return t
    t1, t4 = ticks(1), ticks(4)
    assert t4 < t1, (t1, t4)


def test_swap_installs_replica_by_replica():
    events = []
    b = CNNBatcher(_toy, max_batch=2, step_fn=_STEP, n_replicas=3,
                   on_event=lambda e, kw: events.append((e, kw)))
    b.submit(_reqs((5, 3), 2))
    b.tick()
    b.swap_apply_fn(lambda x: _toy(x) + 1)
    swaps = [kw for e, kw in events if e == "swap"]
    assert [kw["replica"] for kw in swaps] == [0, 1, 2]
    assert all(kw["generation"] == 1 for kw in swaps)
    assert b.generation == 1  # bumped once, not per lane
    b.submit(_reqs((5, 3), 2, rid0=2))
    b.drain()
    assert all(r.generation == 1 for r in b._queues.get((5, 3), [])) or True
    served = [kw for e, kw in events if e == "resolve"]
    assert {kw["replica"] for kw in served} <= {0, 1, 2}


def test_replica_fns_and_step_fn_mutually_exclusive():
    with pytest.raises(ValueError, match="mutually exclusive"):
        CNNBatcher(_toy, step_fn=_STEP, replica_apply_fns=[_toy, _toy],
                   n_replicas=2)
    with pytest.raises(ValueError, match="entries"):
        CNNBatcher(_toy, n_replicas=3, replica_apply_fns=[_toy, _toy])
    with pytest.raises(ValueError, match="entries"):
        CNNBatcher(_toy, n_replicas=2,
                   replica_devices=mesh_mod.replica_devices(3))


# -- windowed wait stats (satellite: SLO sees recent latency) ----------------


def test_windowed_wait_stats_surface_recent_latency():
    """Lifetime percentiles dilute a regression under old history; the
    windowed ones reflect only the last ``wait_window`` samples."""
    b = CNNBatcher(_toy, max_batch=2, max_wait_ticks=3, step_fn=_STEP,
                   wait_window=4)
    # era 1: singletons age past max_wait_ticks before dispatch (history
    # of 3-tick waits)
    for i in range(4):
        b.submit([CNNRequest(rid=i, x=np.ones((5, 3), np.float32))])
        for _ in range(4):
            b.tick()
    # era 2: full buckets flush with zero wait, filling the window
    b.submit(_reqs((5, 3), 2, rid0=100))
    b.tick()
    b.submit(_reqs((5, 3), 2, rid0=102))
    b.tick()
    st = b.stats
    label = next(k for k in st["wait_ticks"] if "(5, 3)" in k)
    life, recent = st["wait_ticks"][label], st["wait_ticks_recent"][label]
    assert life["n"] == 8 and life["max"] >= 3
    assert recent["n"] == 4          # bounded by wait_window
    assert recent["max"] == 0        # the recent era waited zero ticks
    assert recent["p99"] == 0.0 < life["p99"]


# -- per-replica autotune miss attribution -----------------------------------


def test_replica_scope_attributes_misses_and_lint_warns_on_divergence():
    fq_conv.reset_autotune_cache()
    try:
        key_a = (3, 3, 1, "int8")
        key_b = (1, 1, 1, "int8")
        with pytest.warns(fq_conv.AutotuneMissWarning):
            with fq_conv.replica_scope(0):
                fq_conv._note_autotune_miss(key_a)
                fq_conv._note_autotune_miss(key_b)
            with fq_conv.replica_scope(1):
                fq_conv._note_autotune_miss(key_a)  # lane 1 never saw key_b
        assert fq_conv.AUTOTUNE_MISSES_BY_REPLICA == {
            (0, key_a): 1, (0, key_b): 1, (1, key_a): 1}
        report = Report()
        from repro.analysis import kernellint
        kernellint.runtime_miss_counters(report)
        assert report.counters[f"kernellint/runtime-miss:replica[0]:{key_a}"] \
            == 1
        div = [f for f in report.findings
               if f.check == "kernellint/replica-miss-divergence"]
        assert len(div) == 1 and "replica[1]" in div[0].subject
    finally:
        fq_conv.reset_autotune_cache()
    assert fq_conv.AUTOTUNE_MISSES_BY_REPLICA == {}  # reset clears the tags


def test_replica_scope_agreement_is_quiet():
    fq_conv.reset_autotune_cache()
    try:
        key = (3, 3, 1, "int8")
        with pytest.warns(fq_conv.AutotuneMissWarning):
            for tag in (0, 1):
                with fq_conv.replica_scope(tag):
                    fq_conv._note_autotune_miss(key)
        report = Report()
        from repro.analysis import kernellint
        kernellint.runtime_miss_counters(report)
        assert not [f for f in report.findings
                    if f.check == "kernellint/replica-miss-divergence"]
    finally:
        fq_conv.reset_autotune_cache()


# -- the real thing: forced multi-device subprocess --------------------------

_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax, numpy as np
    import jax.numpy as jnp
    assert len(jax.devices()) == 4, jax.devices()

    from repro.core import integer_inference as ii
    from repro.core.quant import QuantConfig
    from repro.launch import mesh as mesh_mod
    from repro.models import kws
    from repro.serve.cnn_batching import CNNBatcher, CNNRequest
    import sys
    sys.path.insert(0, ".")
    from benchmarks.common import trained_int_params

    mesh = mesh_mod.make_serving_mesh(4)
    devs = mesh_mod.replica_devices(4)
    assert len({d.id for d in devs}) == 4  # four DISTINCT devices

    cfg = kws.KWSConfig.reduced()
    qcfg = QuantConfig(2, 4, 4, fq=True)
    _, _, ip = trained_int_params(
        kws, cfg, ["conv%d" % i for i in range(len(cfg.dilations))], qcfg)
    copies = ii.replicate_stack(ip, devs)
    for d, s in zip(devs, copies):
        leaf = jax.tree_util.tree_leaves(s)[0]
        assert next(iter(leaf.devices())) == d, (d, leaf.devices())
        assert ii.stack_digest(s) == ii.stack_digest(ip)

    fns = [kws.int_serve_fn(s, qcfg, cfg) for s in copies]
    b = CNNBatcher(fns[0], max_batch=4, max_wait_ticks=0,
                   dispatch_ahead=True, max_inflight=1,
                   n_replicas=4, replica_apply_fns=fns,
                   replica_devices=devs)
    rng = np.random.default_rng(0)
    reqs = [CNNRequest(rid=i, x=rng.standard_normal(
                (20, cfg.n_mfcc)).astype(np.float32)) for i in range(16)]
    b.submit(reqs)
    while b.outstanding():
        b.tick()
    # replication path against the unplaced single-device stack, one
    # request at a time. The contract is the integer core's codes: each
    # placed copy, at the padded batch of 4 a lane serves, gives every
    # request the codes the reference gives it alone, bit for bit. The
    # float head (decode, classifier, pooling) reduces in another order at
    # batch 4 than at batch 1, which moves a logit by an ulp or two (3e-8
    # at |logit| < 0.5 seen): compared to 1e-5 relative, 1e-6 absolute.
    ref_fn = kws.int_serve_fn(ip, qcfg, cfg)
    for r in reqs:
        want = np.asarray(ref_fn(jnp.asarray(r.x_served)[None]))[0]
        np.testing.assert_allclose(np.asarray(r.out), want,
                                   rtol=1e-5, atol=1e-6)
    xs = np.stack([r.x_served for r in reqs])
    want = [np.asarray(kws.int_core(ip, kws.int_entry(
        ip, jnp.asarray(x)[None], qcfg), qcfg, cfg))[0] for x in xs]
    for d, s in zip(devs, copies):
        for i in range(0, len(xs), 4):
            batch = jax.device_put(xs[i:i + 4], d)
            got = kws.int_core(s, kws.int_entry(s, batch, qcfg), qcfg, cfg)
            assert got.dtype == jnp.int8
            for k, row in enumerate(np.asarray(got)):
                np.testing.assert_array_equal(row, want[i + k])
    st = b.stats
    lanes_used = sum(1 for l in st["replicas"] if l["flushes"])
    assert lanes_used >= 2, st["replicas"]  # load actually spread
    assert st["served"] == 16

    # big-batch DP path: the mesh-sharded step partitions the FP edge
    # reductions, so parity is float-tolerance, not byte equality (the
    # integer core is still exact — docs/SERVING_MESH.md caveats)
    bm = CNNBatcher(fns[0], max_batch=4, max_wait_ticks=0, mesh=mesh)
    reqs2 = [CNNRequest(rid=i, x=r.x) for i, r in enumerate(reqs[:4])]
    bm.submit(reqs2)
    bm.drain()
    for r in reqs2:
        want = np.asarray(ref_fn(jnp.asarray(r.x_served)[None]))[0]
        np.testing.assert_allclose(np.asarray(r.out), want,
                                   rtol=1e-4, atol=1e-5)
    print("MESH_SUBPROCESS_OK", lanes_used)
""")


def test_serving_mesh_subprocess_four_devices():
    """End to end on four forced host devices: serving mesh + distinct
    replica placement + per-replica closures over placed stack copies;
    integer core codes bit-exact vs the unplaced reference stack, logits
    to a few ulp."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_SUBPROCESS_OK" in out.stdout


def test_fleet_replica_placement_failure_raises():
    """A real ConvertedStack that cannot be placed on its replica devices
    fails registration instead of silently sharing one closure."""
    from conftest import trained_int_params
    from repro.core.quant import QuantConfig
    from repro.models import kws
    from repro.serve.fleet import FleetRuntime
    cfg = kws.KWSConfig.reduced()
    qcfg = QuantConfig(2, 4, 4, fq=True)
    _, _, ip = trained_int_params(kws, cfg, kws.conv_names(cfg), qcfg)
    probe = np.zeros((1, cfg.seq_len, cfg.n_mfcc), np.float32)
    fl = FleetRuntime()
    with pytest.raises(ValueError, match="device_put"):
        fl.register("kws", ip, lambda s: kws.int_serve_fn(s, qcfg, cfg),
                    probe=probe, canary_seed=1, n_replicas=2,
                    batcher_kw=dict(replica_devices=["bogus", "bogus"]))
    assert fl.models == ()


_LANE_DEVICES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import sys
    import jax, numpy as np
    assert len(jax.devices()) == 4, jax.devices()
    sys.path.insert(0, ".")
    from benchmarks.common import trained_int_params
    from repro.core.quant import QuantConfig
    from repro.models import kws
    from repro.serve.cnn_batching import CNNBatcher, CNNRequest
    from repro.serve.fleet import FleetRuntime

    cfg = kws.KWSConfig.reduced()
    qcfg = QuantConfig(2, 4, 4, fq=True)
    _, _, ip = trained_int_params(kws, cfg, kws.conv_names(cfg), qcfg)
    builder = lambda s: kws.int_serve_fn(s, qcfg, cfg)
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((8, cfg.seq_len, cfg.n_mfcc)).astype(np.float32)
    kw = dict(max_batch=2, max_wait_ticks=0, dispatch_ahead=True,
              max_inflight=1)
    m = FleetRuntime().register("kws", ip, builder, probe=xs[:2],
                                canary_seed=3, n_replicas=4, batcher_kw=kw)
    reqs = [CNNRequest(rid=i, x=x) for i, x in enumerate(xs)]
    m.batcher.submit(reqs)
    while m.batcher.outstanding():
        m.batcher.tick()
    lanes = m.batcher.stats["replicas"]
    # every lane served, and each lane's results live on its own device
    assert [l["out_devices"] for l in lanes] == [[d.id] for d in m.devices], \\
        lanes
    assert len({d.id for d in m.devices}) == 4

    # one lane on device 0, same batch composition: bit-identical outputs
    one = CNNBatcher(builder(ip), **kw)
    ref = [CNNRequest(rid=i, x=x) for i, x in enumerate(xs)]
    one.submit(ref)
    while one.outstanding():
        one.tick()
    for a, b in zip(reqs, ref):
        np.testing.assert_array_equal(a.out, b.out)
    print("LANE_DEVICES_OK")
""")


def test_fleet_lanes_serve_on_their_own_devices():
    """Four forced host devices: FleetRuntime's four replica lanes each
    produce their results on their own device, bit-identical to a
    one-lane run of the same flushes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _LANE_DEVICES],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LANE_DEVICES_OK" in out.stdout
