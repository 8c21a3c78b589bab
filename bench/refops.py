"""Plain operations for the configurations' reference models.

Written from the FQ-Conv paper's equations (arXiv:1912.09356, eqs. 1-4) in
straightforward ``jax.numpy``; nothing here imports the system under test.
Integer layers accumulate small integer codes exactly: codes and ternary
weights are exact in bfloat16, and every accumulator stays below 2**24, so
a float32 accumulation of their products is the integer sum.

``edge`` selects how a float edge layer (first conv, embedding, head) is
computed:

* ``"highest"`` -- float32-accurate, the precision the configurations state;
* ``"bf16x3"`` -- the control: three bfloat16 products with float32
  accumulation (hi*hi + hi*lo + lo*hi), which is what a TPU's ``high``
  precision computes. Written out so that it reads the same on any backend.

A reference closes over its checkpoint, so XLA folds the scalar
arithmetic on it (e^-s of the entry quantizer, the BN factors) at compile
time, as it does in the served step, which closes over its stack.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

EDGES = ("highest", "bf16x3")


def n_levels(bits: int) -> int:
    """Positive quantizer levels (paper eq. 1): 2**(bits-1) - 1."""
    return 2 ** (bits - 1) - 1


def letterbox(x: np.ndarray, target) -> np.ndarray:
    """Center-crop or zero-pad the leading axes of ``x`` to ``target``; an
    odd excess or deficit goes to the trailing side."""
    for axis, t in enumerate(target):
        cur = x.shape[axis]
        if cur > t:
            lo = (cur - t) // 2
            x = np.take(x, np.arange(lo, lo + t), axis=axis)
        elif cur < t:
            lo = (t - cur) // 2
            widths = [(0, 0)] * x.ndim
            widths[axis] = (lo, t - cur - lo)
            x = np.pad(x, widths)
    return x


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def edge_matmul(a, w, edge: str):
    """Float edge matmul ``a @ w`` at the precision ``edge`` names."""
    if edge == "highest":
        return jnp.matmul(a, w, precision=lax.Precision.HIGHEST)
    if edge != "bf16x3":
        raise ValueError(f"unknown edge precision {edge!r}; one of {EDGES}")
    a_hi, a_lo = _split_bf16(a)
    w_hi, w_lo = _split_bf16(w)

    def dot(p, q):
        return jnp.matmul(p, q, preferred_element_type=jnp.float32)

    return dot(a_hi, w_hi) + (dot(a_hi, w_lo) + dot(a_lo, w_hi))


def edge_conv(x, w, edge: str):
    """Float edge conv, NHWC input, HWIO kernel, SAME padding, at the
    precision ``edge`` names."""
    def conv(a, b, **kw):
        return lax.conv_general_dilated(
            a, b, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            **kw)

    if edge == "highest":
        return conv(x, w, precision=lax.Precision.HIGHEST)
    if edge != "bf16x3":
        raise ValueError(f"unknown edge precision {edge!r}; one of {EDGES}")
    x_hi, x_lo = _split_bf16(x)
    w_hi, w_lo = _split_bf16(w)
    f32 = dict(preferred_element_type=jnp.float32)
    return conv(x_hi, w_hi, **f32) + (conv(x_hi, w_lo, **f32)
                                      + conv(x_lo, w_hi, **f32))


def quantize(x, s, *, bits: int, lo: float):
    """Paper eq. 1 on real values: round(clip(x / e^s, lo, 1) * n), as a
    multiply by e^-s."""
    return jnp.round(jnp.clip(x * jnp.exp(-s), lo, 1.0) * n_levels(bits))


def weight_codes(w, s_w, *, bits: int):
    """Integer weight codes round(clip(w / e^s_w, -1, 1) * n_w)."""
    return jnp.round(jnp.clip(w / jnp.exp(s_w), -1.0, 1.0) * n_levels(bits))


def rescale(s_in, s_w, s_out, *, bits_a: int, bits_w: int, bits_out: int):
    """Paper eq. 4's one scalar per layer: int32 sums -> next layer's bins."""
    n_a, n_w, n_o = (n_levels(b) for b in (bits_a, bits_w, bits_out))
    return jnp.exp(s_in + s_w - s_out) * (n_o / (n_a * n_w))


def int_conv(codes, wc, *, padding, dilation: int = 1):
    """Exact integer conv of codes with integer weights: NHWC/HWIO for 2-D,
    NWC/WIO for 1-D. Returns float32 holding the integer sums."""
    nd = codes.ndim - 2
    dn = ("NHWC", "HWIO", "NHWC") if nd == 2 else ("NWC", "WIO", "NWC")
    return lax.conv_general_dilated(
        codes.astype(jnp.bfloat16), wc.astype(jnp.bfloat16), (1,) * nd,
        padding, rhs_dilation=(dilation,) * nd, dimension_numbers=dn,
        preferred_element_type=jnp.float32)


def requant(acc, scale, *, n_out: int, lo: int = 0):
    return jnp.clip(jnp.round(acc * scale), lo, n_out)


def maxpool2(x):
    """2x2 stride-2 VALID max pool over NHWC."""
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


def he_normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * np.sqrt(2.0 / fan_in)


def ternary_scale(w):
    """Log-scale that makes about half of a weight tensor's ternary codes
    non-zero: e^s = 2 * median|w| puts the rounding threshold at the median."""
    return jnp.log(2.0 * jnp.median(jnp.abs(w)))


def out_scale(acc, s_in, s_w, *, bits_a, bits_w, q=0.99):
    """Log-scale s_out that maps the ``q`` quantile of a layer's integer sums
    onto its top output code, so that codes spread over every level."""
    n_a, n_w = n_levels(bits_a), n_levels(bits_w)
    top = jnp.maximum(jnp.quantile(acc, q), 1.0)
    # rescale(s_in, s_w, s_out) == n_out / top
    return s_in + s_w + jnp.log(top) - jnp.log(float(n_a * n_w))


# bytes per weight code in each storage format of the system
CODE_BYTES = {"int8": 1.0, "int4": 0.5, "ternary": 0.25}


def run_blocks(fwd, payloads, target, block):
    """``fwd`` over the payloads letterboxed to ``target``, in blocks of
    ``block`` rows (the last one zero-padded); returns numpy rows."""
    x = np.stack([letterbox(p, target) for p in payloads])
    x = np.concatenate([x, np.zeros((-len(x) % block,) + x.shape[1:],
                                    x.dtype)])
    out = [np.asarray(fwd(x[i:i + block])) for i in range(0, len(x), block)]
    return np.concatenate(out)[:len(payloads)]


def logit_gap(out: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: the widest |out - ref| over the classes, as a share of the
    reference's logit span (max - min) on that row."""
    span = ref.max(-1) - ref.min(-1)
    return np.abs(out - ref).max(-1) / span
