"""Keyword-spotting network (paper §4.2, Figure 2).

MFCC frames -> small FP fully-connected embedding (N=100) -> BN -> 4-bit
quantize -> 7 dilated FQ-Conv1d layers (45 filters, k=3, VALID padding,
exponential dilation) -> global average pool -> FP softmax head.
~50K params / 3.5M MACs at the paper's input length.

Note: the paper's 1 s clips give ~99 MFCC frames but its dilation ladder
implies a receptive field of 129; we keep the ladder and default the
(synthetic) input length to 140 frames so VALID padding stays well-defined.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import fq_layers as fql
from ..core.noise import NoiseConfig
from ..core.quant import QuantConfig, RELU_BOUND


@dataclasses.dataclass(frozen=True)
class KWSConfig:
    n_mfcc: int = 39
    embed: int = 100
    filters: int = 45
    ksize: int = 3
    dilations: Tuple[int, ...] = (1, 1, 2, 4, 8, 16, 32)
    num_classes: int = 12
    seq_len: int = 140

    @classmethod
    def reduced(cls):
        return cls(n_mfcc=8, embed=16, filters=8,
                   dilations=(1, 1, 2), num_classes=4, seq_len=24)


def init(key, cfg: KWSConfig):
    keys = jax.random.split(key, 3 + len(cfg.dilations))
    params = {"embed": fql.init_dense(keys[0], cfg.n_mfcc, cfg.embed)}
    bn_p, bn_s = fql.init_batchnorm(cfg.embed)
    params["embed_bn"] = bn_p
    state = {"embed_bn": bn_s}
    cin = cfg.embed
    for i, _ in enumerate(cfg.dilations):
        params[f"conv{i}"] = fql.init_fq_conv1d(keys[1 + i], cfg.ksize, cin,
                                                cfg.filters)
        bn_p, bn_s = fql.init_batchnorm(cfg.filters)
        params[f"bn{i}"] = bn_p
        state[f"bn{i}"] = bn_s
        cin = cfg.filters
    params["head"] = fql.init_dense(keys[-1], cfg.filters, cfg.num_classes)
    return params, state


def apply(params, state, x, qcfg: QuantConfig, cfg: KWSConfig, *,
          train: bool = False, rng=None,
          noise: Optional[NoiseConfig] = None):
    """x: (B, T, n_mfcc) -> logits (B, num_classes)."""
    new_state = dict(state)
    # FP expansive embedding (paper keeps this layer full precision).
    h = fql.dense(params["embed"], x)
    h, new_state["embed_bn"] = fql.batchnorm(
        params["embed_bn"], state["embed_bn"], h, train=train)
    rngs = jax.random.split(rng, len(cfg.dilations)) if rng is not None else \
        [None] * len(cfg.dilations)
    for i, dil in enumerate(cfg.dilations):
        # Input quantization of the conv (4-bit entry quantize in Fig 2 is
        # the first conv's input quantizer).
        h = fql.fq_conv1d(
            params[f"conv{i}"], h, qcfg, dilation=dil, padding="VALID",
            b_in=RELU_BOUND, relu_out=True, noise=noise, rng=rngs[i])
        if not qcfg.fq:
            # Pre-FQ training: BN + ReLU after each quantized conv.
            h, new_state[f"bn{i}"] = fql.batchnorm(
                params[f"bn{i}"], state[f"bn{i}"], h, train=train)
            h = jax.nn.relu(h)
    h = jnp.mean(h, axis=1)  # FP global average pool (paper §3.4)
    return fql.dense(params["head"], h), new_state


def to_fq(params, state, cfg: KWSConfig):
    """Fold per-conv BN into conv weights for FQ retraining (paper §3.4)."""
    new = dict(params)
    for i, _ in enumerate(cfg.dilations):
        new[f"conv{i}"] = fql.fold_bn(params[f"conv{i}"], params[f"bn{i}"],
                                      state[f"bn{i}"])
    return new


# ---------------------------------------------------------------------------
# Integer deployment (paper §3.4: codes layer-to-layer, float only at edges)
# ---------------------------------------------------------------------------
# ONE structure, two interpreters: ``layer_plan`` is the single description
# of the integer conv core; ``int_apply`` walks it integer-in/integer-out
# (serving), ``qat_apply`` walks the SAME plan through core/deploy_qat's
# custom_vjp units (deployment-in-the-loop retraining).


def layer_plan(cfg: KWSConfig):
    """The ordered integer core: (layer name, dilation) per conv."""
    return [(f"conv{i}", d) for i, d in enumerate(cfg.dilations)]


def conv_names(cfg: KWSConfig):
    """Names of the code-carrying chain (for sync_handoff / rederive)."""
    return [name for name, _ in layer_plan(cfg)]


def _layer_rngs(rng, n):
    return jax.random.split(rng, n) if rng is not None else [None] * n


def int_extras(params, state, cfg: KWSConfig):
    """The float-side extras of the deployment stack (FP embedding/BN/
    head + the entry/decode scales). Pass to ``ConvertedStack.rederive``
    when the FP edges retrained alongside the conv core."""
    names = conv_names(cfg)
    return {
        "embed": params["embed"],
        "embed_bn": (params["embed_bn"], state["embed_bn"]),
        "head": params["head"],
        "entry": {"s_in": params["conv0"]["s_in"]},
        "s_out_last": params[names[-1]]["s_out"],
    }


def convert_int(params, state, qcfg: QuantConfig, cfg: KWSConfig,
                weight_format=None):
    """Trained FQ params -> :class:`integer_inference.ConvertedStack`.

    The conv stack collapses to int8 weight codes + one folded rescale per
    layer; the FP embedding/BN/head ride along as extras. The FQ hand-off
    contract s_in[i+1] == s_out[i] is validated at conversion time
    (``integer_inference.sync_handoff`` repairs a violated chain).
    ``weight_format`` ("int4"/"ternary"/"auto"/None) selects packed weight
    storage — see ``integer_inference.convert_stack``.
    """
    from ..core import integer_inference as ii
    names = conv_names(cfg)
    return ii.convert_stack({n: params[n] for n in names}, qcfg,
                            specs=[ii.LayerSpec(n) for n in names],
                            extras=int_extras(params, state, cfg),
                            weight_format=weight_format)


def int_core(ip, codes, qcfg: QuantConfig, cfg: KWSConfig, *, impl=None,
             noise: Optional[NoiseConfig] = None, rng=None,
             mac_chunks: int = 1):
    """The integer segment alone: int8 codes in -> int8 codes out.

    This is the exact op sequence ``int_apply`` runs between the entry
    quantizer and the final dequant (single source of truth: int_apply
    calls it, and ``repro.analysis`` traces it to prove integer purity
    and accumulator safety). The rng split mirrors int_apply's per-layer
    schedule bit-for-bit.
    """
    from ..core import integer_inference as ii
    plan = layer_plan(cfg)
    rngs = _layer_rngs(rng, len(plan))
    for (name, dil), r in zip(plan, rngs):
        codes = ii.int_conv1d(ip[name], codes, ksize=cfg.ksize,
                              dilation=dil, impl=impl, noise=noise,
                              rng=r, mac_chunks=mac_chunks)
    return codes


def int_apply(ip, x, qcfg: QuantConfig, cfg: KWSConfig, *, impl=None,
              noise: Optional[NoiseConfig] = None, rng=None,
              mac_chunks: int = 1):
    """x: (B, T, n_mfcc) -> logits, conv stack integer-in/integer-out.

    ``noise`` + ``rng`` run the paper's §4.4 analog-noise model on the
    INTEGER path: per-layer code-domain weight/activation perturbation
    and in-kernel ADC noise on the MAC accumulator (``mac_chunks`` > 1
    applies the chunked-accumulation mitigation). The FP embedding and
    head stay clean — the noise model covers the analog conv core.
    """
    codes = int_entry(ip, x, qcfg)
    codes = int_core(ip, codes, qcfg, cfg, impl=impl, noise=noise, rng=rng,
                     mac_chunks=mac_chunks)
    return int_exit(ip, codes, qcfg)


def _fp_embed(p, bn, bn_state, x):
    """FP embedding at the edge precision (``fq_layers.edge_precision``),
    then its eval-mode BN."""
    with fql.edge_precision():
        h = fql.dense(p, x)
    h, _ = fql.batchnorm(bn, bn_state, h, train=False)
    return h


def _fp_head(p, h):
    """FP global average pool (paper §3.4), then the FP head at the edge
    precision."""
    h = jnp.mean(h, axis=1)
    with fql.edge_precision():
        return fql.dense(p, h)


def int_entry(ip, x, qcfg: QuantConfig):
    """The float prefix: (B, T, n_mfcc) -> the integer core's entry codes
    (FP embedding + BN, entry quantizer)."""
    from ..core import integer_inference as ii
    h = _fp_embed(ip["embed"], *ip["embed_bn"], x)
    return ii.entry_codes(h, ip["entry"], qcfg, b_in=RELU_BOUND)


def int_exit(ip, codes, qcfg: QuantConfig):
    """The float suffix: last core codes -> logits (decode, global
    average pool, FP head)."""
    from ..core import integer_inference as ii
    return _fp_head(ip["head"],
                    ii.decode_output(codes, ip["s_out_last"], qcfg.bits_out))


def qat_apply(params, state, x, qcfg: QuantConfig, cfg: KWSConfig, *,
              impl=None, noise: Optional[NoiseConfig] = None, rng=None,
              mac_chunks: int = 1):
    """Deployment-in-the-loop forward: value == ``int_apply`` of the
    converted params (same codes, same noise draws for the same
    seed/sigma/``mac_chunks``), gradient == the float FQ/STE path.

    ``params`` must be BN-folded FQ params (post-``to_fq``). Scale
    hand-off is tied structurally (layer i reads layer i-1's s_out), so
    inner stored ``s_in`` go stale during training — sync_handoff before
    converting. One plan, two interpreters: same rng split as int_apply.
    """
    from ..core import deploy_qat as dq
    plan = layer_plan(cfg)
    h = _fp_embed(params["embed"], params["embed_bn"], state["embed_bn"], x)
    rngs = _layer_rngs(rng, len(plan))
    codes, s_prev = None, None
    for (name, dil), r in zip(plan, rngs):
        h, codes = dq.qat_conv1d(params[name], h, codes, qcfg,
                                 ksize=cfg.ksize, dilation=dil, s_in=s_prev,
                                 noise=noise, rng=r, mac_chunks=mac_chunks,
                                 impl=impl)
        s_prev = params[name]["s_out"]
    return _fp_head(params["head"], h)


def int_serve_fn(ip, qcfg: QuantConfig, cfg: KWSConfig, **kw):
    """Fixed-signature closure for serve.cnn_batching: (B, T, n_mfcc) -> logits.

    The KWS stack has no spatial pools (dilated VALID convs + global average
    pool), so it gains from the batch-folded conv grid and the batcher, not
    the fused pool epilogue. ``noise``/``rng`` pass through to int_apply so
    a noise-canary batcher tier can draw a fresh key per flush.
    """
    def fn(x, noise=None, rng=None):
        return int_apply(ip, x, qcfg, cfg, noise=noise, rng=rng, **kw)
    return fn
