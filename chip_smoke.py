"""Smoke run of integer CNN serving on TPU: the system's main path, once.

    python chip_smoke.py             # one chip: DarkNet-19 @224, then KWS
    python chip_smoke.py --chips 4   # DarkNet-19 on four replica lanes

One process, no children. Seeded stand-in weights at the published widths
(DarkNet-19: 224x224x3 in, 1000 classes; KWS: 140 frames x 39 MFCC) go
through the normal entry points: ``convert_int`` -> ``int_serve_fn`` ->
``CNNBatcher`` with a shape ladder and dispatch-ahead. On the chip it
checks that

  * every integer conv runs the fused Pallas kernel, compiled by Mosaic
    (``fq_conv2d`` custom calls in the served step's HLO);
  * the served entry codes match the float FQ model's edge layers run at
    highest matmul precision, to within f32 rounding at bin edges;
  * the served int8 core codes are bit-identical to a Pallas-free jnp
    oracle (im2col + ``kernels.ref.ref_fq_matmul``) on the same entry codes;
  * the served logits agree with the float FQ model (``apply`` of the
    folded params at highest matmul precision), top-1 included.

``--chips 4`` runs only the serving-mesh phase: four lanes, each over its
own ``replicate_stack`` copy on its own chip, compared bit-for-bit with a
one-lane run on chip 0.

The printed times are smoke timings of one run, not benchmark results.
Crashes propagate; failed checks are all reported, then the script exits
non-zero. Only when every check passed does the last stdout line give the
JSON result ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core.quant import QuantConfig  # noqa: E402

# ternary weights, 4-bit activations and outputs: the FQ setting the
# repo's serving stacks use
QCFG = QuantConfig(2, 4, 4, fq=True)

# Served entry codes vs the float FQ model's edge layers at "highest"
# precision. Both sides compute f32-accurate sums, so a code moves only
# where a value lies within f32 rounding of a bin edge, a few per million.
# A TPU's default f32 matmul is one bf16 pass and moves far more codes (the
# run logs how many), which is what this check guards against.
ENTRY_FLIP_LIMIT = 1e-4
# Served logits vs the float FQ model, as a share of the float logit span.
# With equal entry codes the integer core is exact, but the float model
# sums each core layer in f32: a pre-quantizer value within f32 rounding
# of a bin edge lands in the neighbouring bin, and such one-LSB flips
# cascade through the later layers. DarkNet-19's 17 wide layers read
# 3-5% of the span on the chip and 5.8% on CPU, hence 0.15. The seeded
# KWS stack has no such flip: it reads below 1e-7 of the span on the chip,
# and 6.4% with one-pass bf16 edge layers, hence 1e-3. (Other weights can
# flip: the reduced KWS reads 5.6% on CPU.)
LOGIT_RTOL = {"darknet19": 0.15, "kws": 1e-3}
N_REQUESTS = 8
MAX_TICKS = 200


def log(*args):
    print(*args, flush=True)


class Checks:
    """Every check is evaluated and printed; failures end the run."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = ""):
        log(f"check {'PASS' if ok else 'FAIL'} {name}"
            + (f": {detail}" if detail else ""))
        if not ok:
            self.failed.append(name)


def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{devs[0].platform!r} devices")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke --chips {n_chips} found only "
                         f"{len(devs)} TPU devices")
    return devs


def count_cache_events():
    """Counts of persistent compilation-cache hits and misses."""
    import jax
    counts = {"hits": 0, "misses": 0}

    def listener(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


# ---------------------------------------------------------------------------
# The Pallas-free oracle: jnp im2col + ref_fq_matmul over the same plan
# ---------------------------------------------------------------------------


def _ref_conv(patches, lp, shape_out):
    from repro.kernels.ref import ref_fq_matmul
    y = ref_fq_matmul(patches.reshape(-1, patches.shape[-1]), lp["w_codes"],
                      lp["rescale"], n_out=lp["n_out"], lo=lp["lo"])
    return y.reshape(shape_out + (-1,))


def darknet_oracle_core(ip, codes, cfg):
    from repro.kernels import ops
    from repro.models import darknet
    plan = darknet.layer_plan(cfg)
    for step in plan[darknet._split_plan(plan):]:
        if step[0] == "pool":
            codes = ops.maxpool2d(codes)
            continue
        _, name, ks, pooled = step
        patches, ho, wo = ops._im2col_2d(codes, ks, 1, ks // 2)
        codes = _ref_conv(patches, ip[name], (codes.shape[0], ho, wo))
        if pooled:
            codes = ops.maxpool2d(codes)
    return codes


def kws_oracle_core(ip, codes, cfg):
    from repro.kernels import ops
    from repro.models import kws
    for name, dil in kws.layer_plan(cfg):
        patches, t_out = ops._im2col_1d(codes, cfg.ksize, dil)
        codes = _ref_conv(patches, ip[name], (codes.shape[0], t_out))
    return codes


def darknet_ref_entry(params, ip, x, cfg):
    """Entry codes from the float FQ model's prefix (``darknet.apply``: FP
    first conv, then the float pools up to the first integer conv), at the
    caller's matmul precision."""
    from repro.core import fq_layers as fql
    from repro.core import integer_inference as ii
    from repro.core.quant import RELU_BOUND, WEIGHT_BOUND
    from repro.kernels import ops
    h = fql.fq_conv2d(params["conv0"], x, QuantConfig(fq=True),
                      padding="SAME", b_in=WEIGHT_BOUND, relu_out=True)
    for layer in cfg.layers[1:]:
        if layer != "M":
            break
        h = ops.maxpool2d(h)
    return ii.entry_codes(h, ip["entry"], QCFG, b_in=RELU_BOUND)


def kws_ref_entry(params, state, ip, x):
    """Entry codes from the float FQ model's embedding + BN (``kws.apply``),
    at the caller's matmul precision."""
    from repro.core import fq_layers as fql
    from repro.core import integer_inference as ii
    from repro.core.quant import RELU_BOUND
    h = fql.dense(params["embed"], x)
    h, _ = fql.batchnorm(params["embed_bn"], state["embed_bn"], h,
                         train=False)
    return ii.entry_codes(h, ip["entry"], QCFG, b_in=RELU_BOUND)


# ---------------------------------------------------------------------------
# Serving phases
# ---------------------------------------------------------------------------


def serve(batcher, reqs):
    """Submit, tick until every request resolved; returns wall seconds."""
    t0 = time.perf_counter()
    batcher.submit(reqs)
    for _ in range(MAX_TICKS):
        if all(r.done for r in reqs):
            break
        batcher.tick()
    else:
        raise RuntimeError(f"{sum(not r.done for r in reqs)} requests "
                           f"unresolved after {MAX_TICKS} ticks")
    errors = [r.error for r in reqs if r.error is not None]
    if errors:
        raise RuntimeError(f"requests shed: {errors}")
    return time.perf_counter() - t0


def fused_kernel_count(step, spec) -> int:
    """fq_conv2d Mosaic custom calls in the step's compiled HLO."""
    hlo = step.lower(spec).compile().as_text()
    return len(re.findall(r"%fq_conv2d[.\w]* = .*custom_call_target="
                          r"\"tpu_custom_call\"", hlo))


def standin(module, cfg, chain, x_cal):
    """Seeded stand-in for a trained checkpoint: init -> ``to_fq`` ->
    quantizer ranges calibrated on a seeded batch (``fq_layers.calibrate``,
    the repo's FQ-transition recipe) -> hand-off tied along the integer
    chain -> ``convert_int``. One fixed s_out for every layer (the
    benchmarks' stand-in) lets 17 random ternary DarkNet-19 layers decay
    to all-zero codes, which would make the code comparison vacuous."""
    import jax
    from repro.core import fq_layers as fql
    params, state = module.init(jax.random.key(0), cfg)
    params = module.to_fq(params, state, cfg)
    # a layer's range is observed only once its input is live, so each
    # pass can extend the calibrated prefix by as little as one layer
    params = fql.calibrate(lambda p: module.apply(
        p, state, x_cal, QCFG, cfg, train=False), params,
        iters=len(chain) + 1)
    for a, b in zip(chain, chain[1:]):
        params[b]["s_in"] = params[a]["s_out"]
    return params, state, module.convert_int(params, state, QCFG, cfg)


def payloads(rng, shapes):
    from repro.serve.cnn_batching import CNNRequest
    return [CNNRequest(rid=i, x=rng.standard_normal(s).astype(np.float32))
            for i, s in enumerate(shapes)]


def phase_model(name, check, *, module, cfg, layers, ladder, shapes,
                entry_fn, ref_entry_fn, core_fn, oracle_fn, max_batch):
    """Serve one model through CNNBatcher and check it against the oracle
    and the float FQ model. ``entry_fn``/``core_fn``/``oracle_fn`` take
    ``(ip, x_or_codes)``, ``ref_entry_fn`` ``(params, state, ip, x)``;
    ``layers`` names the integer convs in order."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import fq_conv, ops
    from repro.serve.cnn_batching import CNNBatcher

    t0 = time.perf_counter()
    x_cal = np.random.default_rng(1).standard_normal(
        (2,) + shapes[0]).astype(np.float32)
    params, state, ip = standin(module, cfg, layers, x_cal)
    log(f"{name}: stand-in stack built in {time.perf_counter() - t0:.3f}s")
    for lname in layers:
        log(f"{name}: layer {lname} impl={ops.conv_impl()}")

    serve_fn = module.int_serve_fn(ip, QCFG, cfg)
    step = jax.jit(serve_fn, donate_argnums=(0,))
    batcher = CNNBatcher(serve_fn, step_fn=step, ladder=ladder,
                         max_batch=max_batch, max_wait_ticks=0,
                         dispatch_ahead=True, max_inflight=2)
    rng = np.random.default_rng(0)
    reqs = payloads(rng, shapes)
    first = serve(batcher, reqs)
    again = payloads(rng, [reqs[0].x_served.shape] * len(reqs))
    steady = serve(batcher, again)
    st = batcher.stats
    log(f"{name}: smoke timing (one run, not a benchmark): first "
        f"{len(reqs)} requests incl. compile {first:.3f}s, next "
        f"{len(again)} {steady:.3f}s; flushes={st['flushes']} "
        f"ladder_normalized={st['ladder_normalized']} "
        f"ladder_misses={st['ladder_misses']}")
    check(f"{name}/ladder", st["ladder_misses"] == 0
          and st["ladder_normalized"] >= 1,
          f"normalized={st['ladder_normalized']} "
          f"misses={st['ladder_misses']}")

    served_x = np.stack([r.x_served for r in reqs])
    spec = jax.ShapeDtypeStruct((max_batch,) + served_x.shape[1:],
                                jnp.float32)
    t0 = time.perf_counter()
    n_fused = fused_kernel_count(step, spec)
    log(f"{name}: AOT compile for HLO inspection {time.perf_counter() - t0:.3f}s")
    check(f"{name}/fused-kernels", n_fused == len(layers),
          f"{n_fused} fq_conv2d Mosaic calls for {len(layers)} integer convs")

    codes_in = jax.jit(lambda x: entry_fn(ip, x))(served_x)

    def ref_entry(precision):
        with jax.default_matmul_precision(precision):
            return np.asarray(jax.jit(
                lambda x: ref_entry_fn(params, state, ip, x))(served_x))

    ref_codes = ref_entry("highest")
    flips = int(np.sum(np.asarray(codes_in) != ref_codes))
    check(f"{name}/entry-codes-vs-float-fq",
          flips <= ENTRY_FLIP_LIMIT * ref_codes.size,
          f"{flips} of {ref_codes.size} entry codes differ from the float "
          f"FQ edge at 'highest', limit {ENTRY_FLIP_LIMIT} x codes")
    lossy = int(np.sum(ref_entry("default") != ref_codes))
    log(f"{name}: the float FQ edge at the device's default precision "
        f"moves {lossy} of {ref_codes.size} entry codes")
    served_codes = jax.jit(lambda c: core_fn(ip, c))(codes_in)
    oracle_codes = jax.jit(lambda c: oracle_fn(ip, c))(codes_in)
    served_codes, oracle_codes = map(np.asarray, (served_codes, oracle_codes))
    diff = int(np.sum(served_codes != oracle_codes))
    hist = np.bincount(served_codes.ravel().astype(np.int64) + 128)[128:]
    check(f"{name}/codes-vs-oracle",
          served_codes.dtype == np.int8 and diff == 0,
          f"{diff} of {served_codes.size} codes differ; code histogram "
          f"{hist.tolist()}")
    check(f"{name}/codes-nondegenerate",
          len(np.unique(served_codes)) > 2,
          f"{len(np.unique(served_codes))} distinct code values")

    logits = np.stack([r.out for r in reqs])
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda x: module.apply(
            params, state, x, QCFG, cfg, train=False)[0])(served_x))
    oracle_logits = np.asarray(jax.jit(
        lambda c: module.int_exit(ip, c, QCFG))(oracle_codes))
    span = float(np.max(ref) - np.min(ref))
    err = float(np.max(np.abs(logits - ref)))
    rtol = LOGIT_RTOL[name]
    check(f"{name}/logits-vs-float-fq",
          np.all(np.isfinite(logits)) and err <= rtol * span,
          f"max |served - float FQ| {err:.6g}, logit span {span:.6g}, "
          f"limit {rtol} x span")
    top_s, top_r = logits.argmax(-1), ref.argmax(-1)
    check(f"{name}/top1", bool(np.all(top_s == top_r)),
          f"served {top_s.tolist()} float FQ {top_r.tolist()}")
    err_o = float(np.max(np.abs(logits - oracle_logits)))
    check(f"{name}/logits-vs-oracle-tail", err_o <= 1e-5 * max(span, 1.0),
          f"max |served - oracle codes through int_exit| {err_o:.6g}")
    log(f"{name}: autotune misses {sorted(fq_conv.AUTOTUNE_MISSES)}")


def darknet_phase(check, max_batch=4):
    from repro.models import darknet, frontends
    cfg = darknet.DarkNetConfig()
    shapes = [(224, 224, 3)] * (N_REQUESTS - 1) + [(200, 260, 3)]
    phase_model(
        "darknet19", check, module=darknet, cfg=cfg,
        layers=darknet.int_conv_names(cfg),
        ladder=frontends.darknet_serving_ladder(cfg, [224]), shapes=shapes,
        entry_fn=lambda ip, x: darknet.int_entry(ip, x, QCFG, cfg),
        ref_entry_fn=lambda p, s, ip, x: darknet_ref_entry(p, ip, x, cfg),
        core_fn=lambda ip, c: darknet.int_core(ip, c, QCFG, cfg),
        oracle_fn=lambda ip, c: darknet_oracle_core(ip, c, cfg),
        max_batch=max_batch)


def kws_phase(check, max_batch=4):
    from repro.models import frontends, kws
    cfg = kws.KWSConfig()
    shapes = [(140, 39)] * (N_REQUESTS - 1) + [(120, 39)]
    phase_model(
        "kws", check, module=kws, cfg=cfg,
        layers=kws.conv_names(cfg),
        ladder=frontends.kws_serving_ladder(cfg, [140]), shapes=shapes,
        entry_fn=lambda ip, x: kws.int_entry(ip, x, QCFG),
        ref_entry_fn=kws_ref_entry,
        core_fn=lambda ip, c: kws.int_core(ip, c, QCFG, cfg),
        oracle_fn=lambda ip, c: kws_oracle_core(ip, c, cfg),
        max_batch=max_batch)


def mesh_phase(check, n: int):
    """DarkNet-19 on n replica lanes, each over its own placed stack copy
    on its own chip, vs one lane on chip 0 serving the same flushes."""
    from repro.core.integer_inference import replicate_stack
    from repro.launch.mesh import replica_devices
    from repro.models import darknet, frontends
    from repro.serve.cnn_batching import CNNBatcher

    cfg = darknet.DarkNetConfig()
    x_cal = np.random.default_rng(1).standard_normal(
        (2, 224, 224, 3)).astype(np.float32)
    _, _, ip = standin(darknet, cfg, darknet.int_conv_names(cfg), x_cal)
    devs = replica_devices(n)
    check("mesh/distinct-devices", len({d.id for d in devs}) == n,
          f"lane devices {[d.id for d in devs]}")
    kw = dict(ladder=frontends.darknet_serving_ladder(cfg, [224]),
              max_batch=2, max_wait_ticks=0, dispatch_ahead=True,
              max_inflight=1)
    lanes = CNNBatcher(
        darknet.int_serve_fn(ip, QCFG, cfg), n_replicas=n,
        replica_apply_fns=[darknet.int_serve_fn(s, QCFG, cfg)
                           for s in replicate_stack(ip, devs)],
        replica_devices=devs, **kw)
    one = CNNBatcher(darknet.int_serve_fn(ip, QCFG, cfg), **kw)
    shapes = [(224, 224, 3)] * (2 * n - 1) + [(200, 260, 3)]
    reqs = payloads(np.random.default_rng(1), shapes)
    ref = payloads(np.random.default_rng(1), shapes)
    t_lanes = serve(lanes, reqs)
    t_one = serve(one, ref)
    log(f"mesh: smoke timing (one run, not a benchmark): {n} lanes "
        f"{t_lanes:.3f}s, one lane {t_one:.3f}s, {len(reqs)} requests, "
        "compiles included")
    st = lanes.stats["replicas"]
    for lane in st:
        log(f"mesh: lane {lane['replica']} device {lane['device']} "
            f"flushes {lane['flushes']} results on {lane['out_devices']}")
    check("mesh/lanes-on-own-devices",
          [lane["out_devices"] for lane in st] == [[d.id] for d in devs],
          "each lane's results live on its own device")
    same = all(np.array_equal(a.out, b.out) for a, b in zip(reqs, ref))
    check("mesh/bit-identical-to-one-lane", same,
          f"{len(reqs)} requests, {n} lanes vs one lane on device 0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devs = require_tpu(args.chips)
    from repro import compile_cache
    from repro.kernels import ops
    cache_dir = compile_cache.enable()
    cache = count_cache_events()
    log(f"device_kind={devs[0].device_kind} platform={devs[0].platform} "
        f"count={len(devs)} jax={jax.__version__}")
    log(f"compile cache: {cache_dir}")

    check = Checks()
    check("no-interpret", not ops._interpret(),
          "Pallas kernels compile with Mosaic, not the interpreter")
    check("conv-impl", ops.conv_impl() == "fused",
          f"conv_impl()={ops.conv_impl()!r}")
    if args.chips == 1:
        darknet_phase(check)
        kws_phase(check)
    else:
        mesh_phase(check, args.chips)
    log(f"compile cache events: {cache}")
    if check.failed:
        log(f"FAILED checks: {check.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
