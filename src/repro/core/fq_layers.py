"""FQ layers: the paper's fully-quantized layer contract as JAX functions.

Every layer has three operating modes, selected by :class:`QuantConfig`:

  * FP      — plain float layer (ladder stage 0 / shadow baseline),
  * Q       — QAT: learned-quantized weights + input activations, float MAC,
              output left FP for the following BN + nonlinearity (paper §4,
              "first train the network to low precision with BNs in place"),
  * FQ      — BN removed (folded), output MAC quantized by the learned
              quantizer which doubles as the nonlinearity (b=0 ≈ ReLU,
              b=-1 ≈ hard-tanh). Quantized input -> integer-representable
              MAC -> quantized output (paper §3.4, eq. 4).

Parameters are plain dicts; a full-precision shadow copy of the weights is
the stored parameter (paper §3.1 / Courbariaux et al.) and quantization is
applied in the forward pass with STE gradients.

Noise injection (paper §4.4) hooks in at the three places the paper studies:
quantized weights, quantized input activations, and the MAC result.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .noise import NoiseConfig, add_lsb_noise
from .quant import (QuantConfig, RELU_BOUND, WEIGHT_BOUND, init_scale,
                    learned_quantize)

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def he_normal(key, shape, fan_in, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / fan_in)


def init_fq_linear(key, din: int, dout: int, dtype=jnp.float32):
    w = he_normal(key, (din, dout), din, dtype)
    return {
        "w": w,
        "s_w": init_scale(w),
        "s_in": jnp.float32(0.0),
        "s_out": jnp.float32(0.0),
    }


def init_fq_conv2d(key, ksize: int, cin: int, cout: int, dtype=jnp.float32):
    w = he_normal(key, (ksize, ksize, cin, cout), ksize * ksize * cin, dtype)
    return {
        "w": w,
        "s_w": init_scale(w),
        "s_in": jnp.float32(0.0),
        "s_out": jnp.float32(0.0),
    }


def init_fq_conv1d(key, ksize: int, cin: int, cout: int, dtype=jnp.float32):
    w = he_normal(key, (ksize, cin, cout), ksize * cin, dtype)
    return {
        "w": w,
        "s_w": init_scale(w),
        "s_in": jnp.float32(0.0),
        "s_out": jnp.float32(0.0),
    }


# ---------------------------------------------------------------------------
# The shared FQ forward contract
# ---------------------------------------------------------------------------


# Matmul precision of the float edge layers around an integer stack. A
# TPU's default for f32 is one bf16 pass, which moves entry codes across
# bin edges (0.2% of DarkNet-19's on a v5e). "highest" is f32-accurate for
# convs and dots alike. A dot-algorithm preset such as "BF16_BF16_F32_X6"
# is not: it reaches dots only, and a conv lowers it to the one-pass
# default.
EDGE_PRECISION = "highest"


def edge_precision():
    """The matmul-precision scope of the float edge layers. Every path that
    computes an edge layer enters it — the served ``int_entry``/``int_exit``
    and the deployment-in-the-loop ``qat_apply`` alike — so retraining sees
    the entry codes that serving produces."""
    return jax.default_matmul_precision(EDGE_PRECISION)


# ---------------------------------------------------------------------------
# Activation-range calibration (PTQ-style, used at the FQ transition)
# ---------------------------------------------------------------------------
# After BN folding (paper Fig 3/4B) every quantizer's operating range shifts:
# inputs are no longer batch-normalized and outputs are no longer rescaled.
# Seeding s from weight statistics is wrong by orders of magnitude (see
# fold_bn); the robust initialization is to OBSERVE the ranges: run a batch
# through the folded network un-jitted inside ``calibration(rec)``, which
# records max|x| at every quantizer keyed by the layer-param dict's id, then
# ``apply_calibration`` writes s = log(range) back into the SAME dicts.
# Iterate 2-3x because each layer's range depends on upstream quantizers.

_CAL = threading.local()


@contextlib.contextmanager
def calibration(rec: dict):
    _CAL.rec = rec
    try:
        yield rec
    finally:
        _CAL.rec = None


def _record(p, kind: str, x):
    rec = getattr(_CAL, "rec", None)
    if rec is not None:
        v = float(jnp.max(jnp.abs(x)))
        d = rec.setdefault(id(p), {})
        d[kind] = max(d.get(kind, 0.0), v)


def apply_calibration(params, rec: dict):
    """Write recorded ranges back: s_in/s_out = log(observed max)."""
    def walk(t):
        if isinstance(t, dict):
            if id(t) in rec:
                r = rec[id(t)]
                if "in" in r and "s_in" in t and r["in"] > 0:
                    t["s_in"] = jnp.float32(jnp.log(r["in"]))
                if "out" in r and "s_out" in t and r["out"] > 0:
                    t["s_out"] = jnp.float32(jnp.log(r["out"]))
            for v in t.values():
                walk(v)
        elif isinstance(t, (tuple, list)):
            for v in t:
                walk(v)
    walk(params)
    return params


def calibrate(apply_fn, params, *, iters: int = 3):
    """apply_fn(params) must run the network UN-JITTED on a sample batch."""
    for _ in range(iters):
        rec = {}
        with calibration(rec):
            apply_fn(params)
        params = apply_calibration(params, rec)
    return params


def _split3(rng):
    if rng is None:
        return None, None, None
    return jax.random.split(rng, 3)


def _prepare_operands(p, x, qcfg: QuantConfig, *, b_in: float,
                      noise: Optional[NoiseConfig], rng):
    """Quantize (and optionally perturb) input activations and weights."""
    kw, ka, kmac = _split3(rng)
    w, xa = p["w"], x
    if qcfg.bits_a is not None:
        _record(p, "in", xa)
        xa = learned_quantize(xa, p["s_in"], bits=qcfg.bits_a, b=b_in)
        if noise is not None:
            xa = add_lsb_noise(xa, ka, noise.sigma_a, p["s_in"], qcfg.bits_a)
    if qcfg.bits_w is not None:
        w = learned_quantize(w, p["s_w"], bits=qcfg.bits_w, b=WEIGHT_BOUND)
        if noise is not None:
            w = add_lsb_noise(w, kw, noise.sigma_w, p["s_w"], qcfg.bits_w)
    return xa, w, kmac


def _finish_output(p, y, qcfg: QuantConfig, *, relu_out: bool,
                   noise: Optional[NoiseConfig], kmac):
    """FQ epilogue: MAC noise, then the output quantizer-as-nonlinearity."""
    if not (qcfg.fq and qcfg.bits_out is not None):
        return y  # Q mode: BN + nonlinearity follow outside this layer.
    _record(p, "out", y)
    if noise is not None:
        y = add_lsb_noise(y, kmac, noise.sigma_mac, p["s_out"], qcfg.bits_out)
    b_out = RELU_BOUND if relu_out else WEIGHT_BOUND
    return learned_quantize(y, p["s_out"], bits=qcfg.bits_out, b=b_out)


def fq_linear(p, x, qcfg: QuantConfig, *, b_in: float = WEIGHT_BOUND,
              relu_out: bool = False, noise: Optional[NoiseConfig] = None,
              rng=None):
    """x @ Q(w) with the FQ contract. x: (..., din)."""
    xa, w, kmac = _prepare_operands(p, x, qcfg, b_in=b_in, noise=noise, rng=rng)
    y = jnp.matmul(xa, w.astype(xa.dtype))
    return _finish_output(p, y, qcfg, relu_out=relu_out, noise=noise, kmac=kmac)


def fq_conv2d(p, x, qcfg: QuantConfig, *, stride: int = 1, padding: str = "SAME",
              b_in: float = WEIGHT_BOUND, relu_out: bool = False,
              noise: Optional[NoiseConfig] = None, rng=None):
    """NHWC 2-D convolution with the FQ contract."""
    xa, w, kmac = _prepare_operands(p, x, qcfg, b_in=b_in, noise=noise, rng=rng)
    y = lax.conv_general_dilated(
        xa, w.astype(xa.dtype), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return _finish_output(p, y, qcfg, relu_out=relu_out, noise=noise, kmac=kmac)


def fq_conv1d(p, x, qcfg: QuantConfig, *, dilation: int = 1,
              padding: str = "VALID", b_in: float = WEIGHT_BOUND,
              relu_out: bool = False, noise: Optional[NoiseConfig] = None,
              rng=None):
    """(B, T, C) 1-D convolution (the paper's KWS layers: VALID, dilated)."""
    xa, w, kmac = _prepare_operands(p, x, qcfg, b_in=b_in, noise=noise, rng=rng)
    y = lax.conv_general_dilated(
        xa, w.astype(xa.dtype), (1,), padding, rhs_dilation=(dilation,),
        dimension_numbers=("NTC", "TIO", "NTC"),
    )
    return _finish_output(p, y, qcfg, relu_out=relu_out, noise=noise, kmac=kmac)


# ---------------------------------------------------------------------------
# Batch normalization (the thing FQ mode removes)
# ---------------------------------------------------------------------------


def init_batchnorm(c: int, dtype=jnp.float32):
    params = {"gamma": jnp.ones((c,), dtype), "beta": jnp.zeros((c,), dtype)}
    state = {"mean": jnp.zeros((c,), dtype), "var": jnp.ones((c,), dtype)}
    return params, state


def batchnorm(p, st, x, *, train: bool, momentum: float = 0.9,
              eps: float = 1e-5):
    """BN over all axes but the last. Returns (y, new_state)."""
    axes = tuple(range(x.ndim - 1))
    if train:
        mean = jnp.mean(x, axes)
        var = jnp.var(x, axes)
        new_st = {
            "mean": momentum * st["mean"] + (1 - momentum) * mean,
            "var": momentum * st["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = st["mean"], st["var"]
        new_st = st
    y = (x - mean) * lax.rsqrt(var + eps) * p["gamma"] + p["beta"]
    return y, new_st


def fold_bn(conv_p, bn_p, bn_st, *, eps: float = 1e-5):
    """Fold inference-mode BN into the conv that precedes it (paper §3.4).

    BN(conv(x)) = gamma' * (w (*) x) + beta'  with  gamma' = gamma/sigma.
    The per-channel gamma' scales the conv weights exactly; beta' is dropped
    (the paper trains the network to adapt to the missing shift). The weight
    quant scale s_w is re-initialized for the rescaled weights, and s_out is
    seeded from s_in + log(max|gamma' w|) as a starting range for retraining.
    """
    gamma_p = bn_p["gamma"] * lax.rsqrt(bn_st["var"] + eps)
    w = conv_p["w"] * gamma_p  # broadcast over trailing (out-channel) dim
    new = dict(conv_p)
    new["w"] = w
    new["s_w"] = init_scale(w)
    # Output-range seed from the BN statistics themselves: the folded
    # output y' = gamma' * y_conv is exactly the (shift-dropped) BN output,
    # whose per-channel std is |gamma| — so a ~2.5-sigma quantizer range is
    # e^{s_out} = 2.5 * max|gamma|. (Seeds derived from weight norms are
    # wrong by orders of magnitude and collapse the FQ finetune — caught by
    # the Table-6 benchmark: ||w||_2-seed exploded logits to +-760, max|w|
    # hard-clipped everything.)
    new["s_out"] = jnp.log(2.5 * jnp.max(jnp.abs(
        bn_p["gamma"].astype(jnp.float32))) + 1e-8)
    return new


# ---------------------------------------------------------------------------
# Plain helpers
# ---------------------------------------------------------------------------


def init_dense(key, din, dout, dtype=jnp.float32, bias=True):
    p = {"w": he_normal(key, (din, dout), din, dtype)}
    if bias:
        p["b"] = jnp.zeros((dout,), dtype)
    return p


def dense(p, x):
    y = jnp.matmul(x, p["w"].astype(x.dtype))
    if "b" in p:
        y = y + p["b"]
    return y
