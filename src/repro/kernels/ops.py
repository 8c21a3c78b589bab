"""Public jit'd wrappers around the Pallas kernels.

On CPU (this container) the kernels execute with ``interpret=True`` — the
kernel body runs as traced jnp ops, validating the exact TPU program logic.
On TPU backends the same calls compile to Mosaic.

Also provides the composite inference ops used by FQ layers:
  * rescale/alpha folding (paper eq. 4's scalar factor),
  * FQ conv1d/conv2d behind one dispatch point: the fused implicit-GEMM
    Pallas kernel (kernels/fq_conv.py) on TPU, the im2col + fq_matmul
    composition as the CPU/interpret fallback and parity oracle.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import quant
from .fq_matmul import fq_matmul
from . import fq_conv
from .quantize import quantize_codes


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Conv implementation dispatch (single choke point for all call sites)
# ---------------------------------------------------------------------------

# "fused"  -> the implicit-GEMM Pallas kernel (no patch materialization),
# "im2col" -> patches in HBM + fq_matmul (the parity oracle),
# None     -> auto: fused on TPU, im2col on CPU where the interpreter makes
#            the kh*kw-step fused grid slower than one big matmul.
def _check_impl(impl: Optional[str], source: str) -> Optional[str]:
    if impl not in (None, "fused", "im2col"):
        raise ValueError(
            f"{source} must be 'fused', 'im2col' or unset, got {impl!r}")
    return impl


_CONV_IMPL: Optional[str] = _check_impl(
    os.environ.get("REPRO_CONV_IMPL") or None, "REPRO_CONV_IMPL")


def set_conv_impl(impl: Optional[str]):
    """Override conv dispatch globally ("fused" / "im2col" / None=auto)."""
    global _CONV_IMPL
    _CONV_IMPL = _check_impl(impl, "set_conv_impl()")


def conv_impl(explicit: Optional[str] = None) -> str:
    impl = _check_impl(explicit, "impl") or _CONV_IMPL
    if impl is None:
        impl = "fused" if jax.default_backend() == "tpu" else "im2col"
    return impl


def fold_rescale(s_a, s_w, s_out, *, bits_a: int, bits_w: int, bits_out: int):
    """rescale = e^(s_a + s_w - s_out) * n_out / (n_a * n_w) — one scalar.

    Maps raw int32 accumulators directly onto the next layer's integer bins
    (the "ADC" of the analog design, a single fused multiply on TPU).
    """
    n_a, n_w, n_o = (quant.n_levels(b) for b in (bits_a, bits_w, bits_out))
    return jnp.exp(s_a + s_w - s_out) * (n_o / (n_a * n_w))


def fold_alpha(s_a, s_w, *, bits_a: int, bits_w: int):
    """alpha = e^(s_a + s_w) / (n_a n_w): int32 accumulator -> real value."""
    n_a, n_w = quant.n_levels(bits_a), quant.n_levels(bits_w)
    return jnp.exp(s_a + s_w) / (n_a * n_w)


def int_matmul(a_codes, b_codes, scale, *, epilogue="requant", n_out=7, lo=0,
               bm=128, bn=128, bk=128, noise_sigma_acc=None, noise_seed=None,
               mac_chunks=1, weight_format="int8"):
    return fq_matmul(
        a_codes, b_codes, scale, epilogue=epilogue, n_out=n_out, lo=lo,
        bm=bm, bn=bn, bk=bk, noise_sigma_acc=noise_sigma_acc,
        noise_seed=noise_seed, mac_chunks=mac_chunks, interpret=_interpret(),
        weight_format=weight_format,
    )


def quantize_to_codes(x, s, *, bits: int, b: float, block_rows=256):
    n = quant.n_levels(bits)
    flat = x.reshape(-1, x.shape[-1])
    codes = quantize_codes(
        flat, jnp.exp(-s), n=n, b=b, block_rows=block_rows,
        interpret=_interpret(),
    )
    return codes.reshape(x.shape)


# ---------------------------------------------------------------------------
# Convolution: fused Pallas kernel, with im2col -> fq_matmul as the
# CPU fallback / parity oracle
# ---------------------------------------------------------------------------


def _im2col_1d(x, ksize: int, dilation: int):
    """(B, T, C) -> (B, T_out, ksize*C); valid padding (paper's KWS net)."""
    b, t, c = x.shape
    t_out = t - dilation * (ksize - 1)
    cols = [x[:, i * dilation : i * dilation + t_out, :] for i in range(ksize)]
    return jnp.concatenate(cols, axis=-1), t_out


def fq_conv1d_int(a_codes, w_codes, scale, *, ksize: int, dilation: int = 1,
                  epilogue="requant", n_out=7, lo=0, impl=None,
                  noise_sigma_acc=None, noise_seed=None, mac_chunks=1,
                  weight_format="int8"):
    """int8 1-D convolution behind the conv dispatch point.

    a_codes: (B, T, Cin) int8; w_codes: (ksize*Cin, Cout) int8, or the
    ``weight_format`` packed uint8 layout (core.quant.pack_im2col_codes).
    The fused kernel consumes packed weights natively; the im2col impl
    unpacks to the int8 layout first, so it remains the single parity
    oracle for every weight format. ``noise_sigma_acc``/``noise_seed``/
    ``mac_chunks`` switch on the deterministic ADC-noise epilogue (paper
    §4.4) on BOTH impls — the noise field is indexed by global output
    elements, so fused and im2col stay bit-identical under noise.
    """
    if conv_impl(impl) == "fused":
        return fq_conv.fq_conv1d(
            a_codes, w_codes, scale, ksize=ksize, dilation=dilation,
            epilogue=epilogue, n_out=n_out, lo=lo,
            noise_sigma_acc=noise_sigma_acc, noise_seed=noise_seed,
            mac_chunks=mac_chunks, interpret=_interpret(),
            weight_format=weight_format)
    if weight_format != "int8":
        w_codes = quant.unpack_im2col_codes(
            w_codes, ksize, a_codes.shape[-1], weight_format)
    b = a_codes.shape[0]
    patches, t_out = _im2col_1d(a_codes, ksize, dilation)
    flat = patches.reshape(b * t_out, -1)
    y = int_matmul(flat, w_codes, scale, epilogue=epilogue, n_out=n_out, lo=lo,
                   noise_sigma_acc=noise_sigma_acc, noise_seed=noise_seed,
                   mac_chunks=mac_chunks)
    return y.reshape(b, t_out, -1)


def _im2col_2d(x, ksize: int, stride: int, padding: int, dilation: int = 1):
    """(B, H, W, C) -> (B, Ho, Wo, ksize*ksize*C)."""
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    b, h, w, c = x.shape
    span = dilation * (ksize - 1) + 1
    ho = (h - span) // stride + 1
    wo = (w - span) // stride + 1
    cols = []
    for di in range(ksize):
        for dj in range(ksize):
            oi, oj = di * dilation, dj * dilation
            cols.append(
                x[:, oi : oi + (ho - 1) * stride + 1 : stride,
                  oj : oj + (wo - 1) * stride + 1 : stride, :]
            )
    return jnp.concatenate(cols, axis=-1), ho, wo


def fq_conv2d_int(a_codes, w_codes, scale, *, ksize: int, stride: int = 1,
                  padding: int = 0, dilation: int = 1, epilogue="requant",
                  n_out=7, lo=0, impl=None, noise_sigma_acc=None,
                  noise_seed=None, mac_chunks=1, weight_format="int8"):
    """int8 2-D convolution (NHWC) behind the conv dispatch point.

    w_codes: (ksize*ksize*Cin, Cout) int8, tap-major im2col layout, or
    the ``weight_format`` packed uint8 layout. The fused kernel consumes
    packed weights natively; the im2col impl unpacks back to the int8
    layout first — im2col at int8 stays the parity oracle for every
    format. ``noise_sigma_acc``/``noise_seed``/``mac_chunks``: see
    fq_conv1d_int.
    """
    if conv_impl(impl) == "fused":
        return fq_conv.fq_conv2d(
            a_codes, w_codes, scale, kh=ksize, kw=ksize,
            stride=(stride, stride), padding=(padding, padding),
            dilation=(dilation, dilation), epilogue=epilogue, n_out=n_out,
            lo=lo, noise_sigma_acc=noise_sigma_acc, noise_seed=noise_seed,
            mac_chunks=mac_chunks, interpret=_interpret(),
            weight_format=weight_format)
    if weight_format != "int8":
        w_codes = quant.unpack_im2col_codes(
            w_codes, ksize * ksize, a_codes.shape[-1], weight_format)
    b = a_codes.shape[0]
    patches, ho, wo = _im2col_2d(a_codes, ksize, stride, padding, dilation)
    flat = patches.reshape(b * ho * wo, -1)
    y = int_matmul(flat, w_codes, scale, epilogue=epilogue, n_out=n_out, lo=lo,
                   noise_sigma_acc=noise_sigma_acc, noise_seed=noise_seed,
                   mac_chunks=mac_chunks)
    return y.reshape(b, ho, wo, -1)


def fq_conv2d_add_int(a_codes, w_codes, scale, shortcut, *, ksize: int,
                      n_out: int, lo: int, stride: int = 1, padding: int = 0,
                      dilation: int = 1, impl=None, noise_sigma_acc=None,
                      noise_seed=None, mac_chunks=1, weight_format="int8"):
    """int8 conv2d + residual add + ReLU, fused where the backend can:
    ``clip(clip(round(acc * scale), lo, n_out) + shortcut, 0, n_out)``,
    ``lo`` the branch's signed floor (-n_out on a residual stream).

    ``shortcut`` holds codes shaped like the conv output, on the same
    scale (the residual stream's). "fused" adds them in the kernel's
    epilogue (fq_conv.fq_conv2d ``residual=``); "im2col" composes the
    plain conv with ``integer_inference.int_residual_add`` — the parity
    oracle. ADC noise perturbs the accumulator before the add on both.
    """
    if conv_impl(impl) == "fused":
        return fq_conv.fq_conv2d(
            a_codes, w_codes, scale, kh=ksize, kw=ksize,
            stride=(stride, stride), padding=(padding, padding),
            dilation=(dilation, dilation), n_out=n_out, lo=lo,
            noise_sigma_acc=noise_sigma_acc, noise_seed=noise_seed,
            mac_chunks=mac_chunks, interpret=_interpret(),
            weight_format=weight_format, residual=shortcut)
    from ..core.integer_inference import int_residual_add
    y = fq_conv2d_int(a_codes, w_codes, scale, ksize=ksize, stride=stride,
                      padding=padding, dilation=dilation, n_out=n_out, lo=lo,
                      impl="im2col", noise_sigma_acc=noise_sigma_acc,
                      noise_seed=noise_seed, mac_chunks=mac_chunks,
                      weight_format=weight_format)
    return int_residual_add(y, shortcut, n_out=n_out, lo=0)


def maxpool2d(y, *, window: int = 2, stride: int = 2):
    """VALID maxpool on int8 codes or f32 activations (NHWC).

    On codes this is exact because the learned quantizer is monotone —
    max commutes with (de/re)quantization. Used by the unfused conv+pool
    oracle below, by ``integer_inference.int_maxpool2d``, and (on f32) as
    the differentiable pool of core/deploy_qat's float surrogates.

    The init value must be a HOST constant, not a traced ``jnp.asarray``:
    a tracer-valued reduce_window init breaks ``jax.vjp`` linearization
    inside jit (unknown-primal assertion), which the QAT backward hits.
    """
    init = np.asarray(-128 if y.dtype == jnp.int8 else -np.inf, y.dtype)
    return jax.lax.reduce_window(
        y, init, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def fq_conv2d_pool_int(a_codes, w_codes, scale, *, ksize: int, stride: int = 1,
                       padding: int = 0, dilation: int = 1, pool: int = 2,
                       epilogue="requant", n_out=7, lo=0, impl=None,
                       noise_sigma_acc=None, noise_seed=None, mac_chunks=1,
                       weight_format="int8"):
    """int8 conv2d + non-overlapping maxpool, fused where the backend can.

    "fused" runs the pool on the int32 accumulator tile inside the kernel's
    VMEM epilogue (fq_conv.fq_conv2d ``pool=``) so only Ho*Wo/pool**2 output
    bytes reach HBM; "im2col" composes the unfused conv with a code-domain
    reduce_window — the parity oracle (bit-exact because the quantizer is
    monotone, so max commutes with requant). With the ADC-noise epilogue
    on, the fused path perturbs the PRE-POOL accumulator and the im2col
    path perturbs the pre-pool conv output — max still commutes, so the
    two stay bit-identical under noise.
    """
    if conv_impl(impl) == "fused":
        return fq_conv.fq_conv2d(
            a_codes, w_codes, scale, kh=ksize, kw=ksize,
            stride=(stride, stride), padding=(padding, padding),
            dilation=(dilation, dilation), pool=(pool, pool),
            epilogue=epilogue, n_out=n_out, lo=lo,
            noise_sigma_acc=noise_sigma_acc, noise_seed=noise_seed,
            mac_chunks=mac_chunks, interpret=_interpret(),
            weight_format=weight_format)
    y = fq_conv2d_int(a_codes, w_codes, scale, ksize=ksize, stride=stride,
                      padding=padding, dilation=dilation, epilogue=epilogue,
                      n_out=n_out, lo=lo, impl="im2col",
                      noise_sigma_acc=noise_sigma_acc, noise_seed=noise_seed,
                      mac_chunks=mac_chunks, weight_format=weight_format)
    return maxpool2d(y, window=pool, stride=pool)
