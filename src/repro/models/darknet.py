"""DarkNet-19 (paper §4.1 Table 3; Redmon & Farhadi 2016).

19 conv layers (3x3 / 1x1 alternating), BN + leaky-ReLU(0.1) after each,
maxpool between stages, 1x1xC classifier conv, global average pool. In FQ
mode the BN+leaky-ReLU pairs become quantized ReLUs (b=0); first and last
layers stay full precision per the paper's ImageNet protocol.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import fq_layers as fql
from ..core.noise import NoiseConfig
from ..core.quant import QuantConfig, RELU_BOUND, WEIGHT_BOUND

# (ksize, cout) per conv; "M" = 2x2 maxpool stride 2.
_DARKNET19 = [
    (3, 32), "M", (3, 64), "M", (3, 128), (1, 64), (3, 128), "M",
    (3, 256), (1, 128), (3, 256), "M",
    (3, 512), (1, 256), (3, 512), (1, 256), (3, 512), "M",
    (3, 1024), (1, 512), (3, 1024), (1, 512), (3, 1024),
]


@dataclasses.dataclass(frozen=True)
class DarkNetConfig:
    layers: Tuple = tuple(_DARKNET19)
    num_classes: int = 1000
    in_channels: int = 3

    @classmethod
    def reduced(cls):
        return cls(layers=((3, 8), "M", (3, 16), "M", (3, 16), (1, 8), (3, 16)),
                   num_classes=16)


def init(key, cfg: DarkNetConfig):
    params, state = {}, {}
    convs = [l for l in cfg.layers if l != "M"]
    keys = jax.random.split(key, len(convs) + 1)
    cin = cfg.in_channels
    for i, (ks, cout) in enumerate(convs):
        params[f"conv{i}"] = fql.init_fq_conv2d(keys[i], ks, cin, cout)
        p, s = fql.init_batchnorm(cout)
        params[f"bn{i}"], state[f"bn{i}"] = p, s
        cin = cout
    params["head"] = fql.init_fq_conv2d(keys[-1], 1, cin, cfg.num_classes)
    return params, state


def apply(params, state, x, qcfg: QuantConfig, cfg: DarkNetConfig, *,
          train: bool = False, rng=None,
          noise: Optional[NoiseConfig] = None):
    """x: (B, H, W, 3) -> logits (B, num_classes)."""
    new_state = dict(state)
    convs = [l for l in cfg.layers if l != "M"]
    rngs = iter(jax.random.split(rng, len(convs))) if rng is not None else None
    h, ci = x, 0
    fp = QuantConfig(fq=qcfg.fq)
    for layer in cfg.layers:
        if layer == "M":
            h = -jax.lax.reduce_window(-h, jnp.inf, jax.lax.min, (1, 2, 2, 1),
                                       (1, 2, 2, 1), "VALID")
            continue
        lq = fp if ci == 0 else qcfg  # first conv stays FP (paper protocol)
        b_in = WEIGHT_BOUND if ci == 0 else RELU_BOUND
        h = fql.fq_conv2d(params[f"conv{ci}"], h, lq, padding="SAME",
                          b_in=b_in, relu_out=True, noise=noise,
                          rng=next(rngs) if rngs is not None else None)
        if not lq.fq:
            h, new_state[f"bn{ci}"] = fql.batchnorm(
                params[f"bn{ci}"], state[f"bn{ci}"], h, train=train)
            h = jax.nn.leaky_relu(h, 0.1)
        ci += 1
    # Last (classifier) conv stays FP; GAP + softmax head outside.
    h = fql.fq_conv2d(params["head"], h, QuantConfig(), padding="SAME",
                      b_in=RELU_BOUND)
    return jnp.mean(h, axis=(1, 2)), new_state


def to_fq(params, state, cfg: DarkNetConfig):
    new = dict(params)
    for name in list(params):
        if f"bn{name[4:]}" in params and name.startswith("conv"):
            i = name[4:]
            new[name] = fql.fold_bn(params[name], params[f"bn{i}"],
                                    state[f"bn{i}"])
    return new


# ---------------------------------------------------------------------------
# Integer deployment (paper §3.4). First/last convs stay FP per the paper's
# ImageNet protocol; everything between runs integer-in/integer-out,
# maxpools included (the monotone quantizer commutes with max, so pooling
# operates on int8 codes directly — integer_inference.int_maxpool2d).
#
# ONE structure, two interpreters: ``layer_plan`` compiles cfg.layers into
# the ordered op list (FP edge conv, pools, integer convs with the fused
# conv+pool lookahead resolved); ``int_apply`` walks it on codes (serving)
# and ``qat_apply`` walks the SAME plan through core/deploy_qat's units
# (deployment-in-the-loop retraining) — the duplicated while-loop walks
# this plan replaces.
# ---------------------------------------------------------------------------


def layer_plan(cfg: DarkNetConfig, fuse_pool: bool = True):
    """cfg.layers -> ordered steps:

    ``("fp_conv", ks)`` FP first conv; ``("pool",)`` standalone maxpool
    (float before entry, code-domain after); ``("conv", name, ks, pooled)``
    integer conv, ``pooled=True`` when the following "M" fused into its
    epilogue (consumed from the walk).
    """
    plan, layers, ci, i = [], list(cfg.layers), 0, 0
    while i < len(layers):
        layer = layers[i]
        if layer == "M":
            plan.append(("pool",))
            i += 1
            continue
        ks, _ = layer
        if ci == 0:
            plan.append(("fp_conv", ks))
        else:
            pooled = fuse_pool and i + 1 < len(layers) and \
                layers[i + 1] == "M"
            plan.append(("conv", f"conv{ci}", ks, pooled))
            if pooled:
                i += 1  # the pool is consumed by the fused epilogue
        ci += 1
        i += 1
    return plan


def int_conv_names(cfg: DarkNetConfig):
    """Names of the code-carrying chain (for sync_handoff / rederive)."""
    return [s[1] for s in layer_plan(cfg) if s[0] == "conv"]


def _layer_rngs(rng, n):
    return list(jax.random.split(rng, n)) if rng is not None else [None] * n


def int_extras(params, state, cfg: DarkNetConfig):
    """Float-side extras (FP edge convs + entry/decode scales); pass to
    ``ConvertedStack.rederive`` when the FP edges retrained too."""
    names = int_conv_names(cfg)
    return {"conv0": params["conv0"], "head": params["head"],
            "entry": {"s_in": params[names[0]]["s_in"]},
            "s_out_last": params[names[-1]]["s_out"]}


def convert_int(params, state, qcfg: QuantConfig, cfg: DarkNetConfig,
                weight_format=None):
    """Trained FQ (BN-folded) params -> ConvertedStack (integer core +
    the FP edge convs as extras). Validates the FQ hand-off contract.
    ``weight_format`` ("int4"/"ternary"/"auto"/None) selects packed
    weight storage — see ``integer_inference.convert_stack``."""
    from ..core import integer_inference as ii
    names = int_conv_names(cfg)
    return ii.convert_stack({n: params[n] for n in names}, qcfg,
                            specs=[ii.LayerSpec(n) for n in names],
                            extras=int_extras(params, state, cfg),
                            weight_format=weight_format)


def _split_plan(plan):
    """Index of the first integer conv step — the entry of the code core.

    Steps before it are the FP prefix (edge conv + pre-entry float pools);
    every step from it onward operates on int8 codes.
    """
    for i, step in enumerate(plan):
        if step[0] == "conv":
            return i
    return len(plan)


def int_core(ip, codes, qcfg: QuantConfig, cfg: DarkNetConfig, *, impl=None,
             fuse_pool: bool = True, noise: Optional[NoiseConfig] = None,
             rng=None, mac_chunks: int = 1):
    """The integer segment alone: int8 codes in -> int8 codes out.

    Walks the code-domain suffix of ``layer_plan`` (integer convs, fused
    or standalone code pools). Single source of truth: ``int_apply``
    calls it, and ``repro.analysis`` traces it to prove integer purity
    and accumulator safety. The rng split mirrors int_apply's per-conv
    schedule bit-for-bit ("conv" steps only exist in this suffix).
    """
    from ..core import integer_inference as ii
    plan = layer_plan(cfg, fuse_pool)
    core = plan[_split_plan(plan):]
    rngs = _layer_rngs(rng, sum(1 for s in core if s[0] == "conv"))
    li = 0
    for step in core:
        if step[0] == "pool":
            codes = ii.int_maxpool2d(codes)
        else:
            _, name, ks, pooled = step
            nkw = dict(ksize=ks, padding=ks // 2, impl=impl, noise=noise,
                       rng=rngs[li], mac_chunks=mac_chunks)
            li += 1
            if pooled:
                codes = ii.int_conv2d_pool(ip[name], codes, **nkw)
            else:
                codes = ii.int_conv2d(ip[name], codes, **nkw)
    return codes


def int_apply(ip, x, qcfg: QuantConfig, cfg: DarkNetConfig, *, impl=None,
              fuse_pool: bool = True, noise: Optional[NoiseConfig] = None,
              rng=None, mac_chunks: int = 1):
    """x: (B, H, W, 3) -> logits; codes flow conv1 -> last conv.

    conv+maxpool pairs on the integer path go through ONE op
    (``integer_inference.int_conv2d_pool``): the pool fuses into the conv
    kernel's VMEM epilogue, so the unpooled int8 plane never round-trips
    HBM. ``fuse_pool=False`` keeps the PR-1 conv-then-pool composition as
    the stack-level parity oracle.

    ``noise`` + ``rng`` run the paper's §4.4 analog-noise model on every
    integer conv (code-domain weight/activation noise + in-kernel ADC
    noise; ``mac_chunks`` > 1 is the chunked-accumulation mitigation).
    The FP first/last convs stay clean per the deployment protocol —
    they never leave the digital domain.
    """
    codes = int_entry(ip, x, qcfg, cfg)
    codes = int_core(ip, codes, qcfg, cfg, impl=impl, fuse_pool=fuse_pool,
                     noise=noise, rng=rng, mac_chunks=mac_chunks)
    return int_exit(ip, codes, qcfg)


def _fp_first_conv(p, x, qcfg: QuantConfig):
    """FP first conv (BN folded into w), in the same fp-in-fq-mode config
    as apply(), at the edge precision (``fq_layers.edge_precision``)."""
    with fql.edge_precision():
        return fql.fq_conv2d(p, x, QuantConfig(fq=qcfg.fq), padding="SAME",
                             b_in=WEIGHT_BOUND)


def _fp_head(p, h):
    """FP classifier conv at the edge precision, then global average pool."""
    with fql.edge_precision():
        h = fql.fq_conv2d(p, h, QuantConfig(), padding="SAME",
                          b_in=RELU_BOUND)
    return jnp.mean(h, axis=(1, 2))


def int_entry(ip, x, qcfg: QuantConfig, cfg: DarkNetConfig):
    """The float prefix: (B, H, W, 3) -> the integer core's entry codes
    (FP edge conv, pre-entry float pools, entry quantizer)."""
    from ..core import integer_inference as ii
    plan = layer_plan(cfg)
    h = x
    for step in plan[:_split_plan(plan)]:
        if step[0] == "fp_conv":
            h = _fp_first_conv(ip["conv0"], h, qcfg)
        else:  # pre-entry float pool
            h = -jax.lax.reduce_window(
                -h, jnp.inf, jax.lax.min, (1, 2, 2, 1), (1, 2, 2, 1),
                "VALID")
    return ii.entry_codes(h, ip["entry"], qcfg, b_in=RELU_BOUND)


def int_exit(ip, codes, qcfg: QuantConfig):
    """The float suffix: last core codes -> logits (decode, FP classifier
    conv, global average pool)."""
    from ..core import integer_inference as ii
    return _fp_head(ip["head"],
                    ii.decode_output(codes, ip["s_out_last"], qcfg.bits_out))


def qat_apply(params, state, x, qcfg: QuantConfig, cfg: DarkNetConfig, *,
              impl=None, fuse_pool: bool = True,
              noise: Optional[NoiseConfig] = None, rng=None,
              mac_chunks: int = 1):
    """Deployment-in-the-loop forward: value == ``int_apply`` of the
    converted params (same codes, same noise draws), gradient == the
    float FQ/STE path. ``params`` must be BN-folded (post-``to_fq``);
    ``state`` is unused (BN is folded) and kept for signature symmetry.
    """
    from ..core import deploy_qat as dq
    from ..kernels import ops
    plan = layer_plan(cfg, fuse_pool)
    rngs = _layer_rngs(rng, sum(1 for s in plan if s[0] == "conv"))
    h, codes, s_prev, li = x, None, None, 0
    for step in plan:
        if step[0] == "fp_conv":
            h = _fp_first_conv(params["conv0"], h, qcfg)
        elif step[0] == "pool":
            if codes is None:
                h = ops.maxpool2d(h)  # pre-entry FP pool (differentiable)
            else:
                h, codes = dq.qat_maxpool2d(h, codes)
        else:
            _, name, ks, pooled = step
            h, codes = dq.qat_conv2d(params[name], h, codes, qcfg,
                                     ksize=ks, pool=2 if pooled else None,
                                     s_in=s_prev, noise=noise, rng=rngs[li],
                                     mac_chunks=mac_chunks, impl=impl)
            s_prev = params[name]["s_out"]
            li += 1
    return _fp_head(params["head"], h)


def int_serve_fn(ip, qcfg: QuantConfig, cfg: DarkNetConfig, **kw):
    """Fixed-signature closure for serve.cnn_batching: (B, H, W, 3) -> logits.

    ``noise``/``rng`` pass through to int_apply so a noise-canary batcher
    tier can draw a fresh key per flush.
    """
    def fn(x, noise=None, rng=None):
        return int_apply(ip, x, qcfg, cfg, noise=noise, rng=rng, **kw)
    return fn
