"""Pallas TPU kernel: fully-quantized integer matmul (paper eq. 4).

    w . a = (s^w s^a / n^w n^a) * sum_i w_i^int a_i^int

TPU adaptation of the paper's analog "integer MAC + ADC binning": int8 codes
stream HBM->VMEM in 128-aligned tiles, the MXU accumulates int8 x int8 into an
int32 VMEM scratch across the K grid, and the requantization "bin" (the ADC in
the analog design) is a fused epilogue — a single rescale + round + clip that
produces the next layer's int8 codes before the tile ever leaves VMEM. The
float factor  e^(s_a + s_w - s_out) * n_out / (n_a n_w)  folds into one scalar.

Epilogue modes:
  * ``requant``  -> int8 codes for the next FQ layer (the common case),
  * ``dequant``  -> f32  alpha * acc  (final layer, feeds FP pooling/softmax).

Grid is (M/bm, N/bn, K/bk) with K innermost ("arbitrary" semantics) so the
accumulator tile stays resident in VMEM for the whole K reduction.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import quant
from ..core.noise import mac_noise_field

def apply_epilogue(acc, scale, *, epilogue: str, n_out: int, lo: int):
    """The fused requant/dequant 'ADC' epilogue on an int32 accumulator.

    Shared by fq_matmul and fq_conv so the two paths are bit-identical:
    codes = clip(round(acc * rescale), lo, n_out) — round/clip commute
    because lo, n_out are ints.
    """
    if epilogue == "requant":
        y = jnp.round(acc.astype(jnp.float32) * scale)
        return jnp.clip(y, lo, n_out).astype(jnp.int8)
    return acc.astype(jnp.float32) * scale  # dequant


def noise_tile(rows, col0, n_cols: int, seed, sigma, mac_chunks: int):
    """ADC-noise tile for a (rows, cols) accumulator block.

    ``rows`` holds each element's GLOBAL output row (same shape as the
    block). The field is indexed by ``row * n_cols + (col0 + j)`` with the
    TRUE (unpadded) column count, so it is independent of tiling/padding
    and the fused conv kernel — which passes its im2col-flattened output
    rows — reproduces it bit-for-bit. Padded rows/cols draw values that
    the caller slices away.
    """
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    return mac_noise_field(rows * n_cols + cols, seed, sigma,
                           chunks=mac_chunks)


def _kernel(scale_ref, a_ref, b_ref, *refs, k_steps: int,
            epilogue: str, n_out: int, lo: int, noise: bool,
            mac_chunks: int, n_true: int, weight_format: str):
    if noise:
        sigma_ref, seed_ref, o_ref, acc_ref = refs
        # program_id reads hoisted out of the pl.when body (interpret
        # mode can't lower the primitive inside the cond).
        i, j = pl.program_id(0), pl.program_id(1)
    else:
        o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = b_ref[...]
    if weight_format != "int8":
        # unpack the (bk/factor, bn) byte tile to (bk, bn) int8 codes in
        # VMEM ahead of the MAC — the accumulator math is then the int8
        # kernel's, bit for bit.
        b = quant.unpack_codes(b, weight_format)
    acc_ref[...] += jnp.dot(
        a_ref[...], b, preferred_element_type=jnp.int32
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        if noise:
            # ADC noise on the accumulator, drawn per GLOBAL output
            # element before the requant bins it — the analog-noise
            # story of paper §4.4 on the TPU epilogue.
            bm, bn = acc.shape
            rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
            acc = acc.astype(jnp.float32) + noise_tile(
                rows, j * bn, n_true, seed_ref[0, 0], sigma_ref[0, 0],
                mac_chunks)
        o_ref[...] = apply_epilogue(
            acc, scale_ref[0, 0], epilogue=epilogue, n_out=n_out, lo=lo)


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "n_out", "lo", "bm", "bn", "bk",
                     "mac_chunks", "interpret", "weight_format"),
)
def fq_matmul(
    a_codes: jax.Array,   # (M, K) int8
    b_codes: jax.Array,   # (K, N) int8; packed formats: (ceil(K/f), N) uint8
    scale: jax.Array,     # scalar f32: rescale (requant) or alpha (dequant)
    *,
    epilogue: str = "requant",
    n_out: int = 7,
    lo: int = 0,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    noise_sigma_acc: Optional[jax.Array] = None,
    noise_seed: Optional[jax.Array] = None,
    mac_chunks: int = 1,
    interpret: bool = False,
    weight_format: str = "int8",
) -> jax.Array:
    """Tiled int8 matmul with fused requantization. Pads to block multiples.

    ``weight_format`` in {"int8", "int4", "ternary"} selects the B-operand
    storage (see ``core.quant.pack_codes``). Packed B arrives as
    (ceil(K/factor), N) uint8 — K may have been padded to a factor
    multiple at pack time with zero codes, which are inert because the
    matching A lanes are zero-padded here. Tiles are unpacked in VMEM
    before the MAC, so accumulator/epilogue/noise behavior is
    bit-identical to the int8 path.

    ``noise_sigma_acc`` (std in ACCUMULATOR units) + ``noise_seed``
    (uint32) switch on the deterministic ADC-noise epilogue (paper §4.4):
    the int32 accumulator is perturbed in VMEM before requant.
    ``mac_chunks=K`` applies the chunked-accumulation mitigation (K
    per-chunk conversions at 1/K range -> effective std / sqrt(K)). With
    ``noise_sigma_acc=None`` the compiled program is the unchanged clean
    kernel — no extra operands, no extra ops.
    """
    assert epilogue in ("requant", "dequant")
    assert mac_chunks >= 1
    noise = noise_sigma_acc is not None
    assert not noise or noise_seed is not None, \
        "noise_seed is required when noise_sigma_acc is set"
    m, k = a_codes.shape
    packed = weight_format != "int8"
    factor = quant.format_factor(weight_format)
    if packed:
        rows_p, n = b_codes.shape
        k2 = rows_p * factor  # stored K incl. pack-time zero padding
        assert 0 <= k2 - k < factor, \
            (a_codes.shape, b_codes.shape, weight_format)
        assert bk % factor == 0, \
            f"bk={bk} must be a multiple of the pack factor {factor}"
    else:
        k2, n = b_codes.shape
        assert k == k2, (a_codes.shape, b_codes.shape)

    mp, np_, kp = (-m % bm), (-n % bn), (-k2 % bk)
    if mp or kp or k2 != k:
        a_codes = jnp.pad(a_codes, ((0, mp), (0, k2 - k + kp)))
    if packed:
        rp = (k2 + kp) // factor - b_codes.shape[0]
        if rp or np_:
            # zero bytes decode to zero codes -> pad lanes stay inert
            b_codes = jnp.pad(b_codes, ((0, rp), (0, np_)))
    elif kp or np_:
        b_codes = jnp.pad(b_codes, ((0, kp), (0, np_)))
    pm, pn, pk = m + mp, n + np_, k2 + kp
    k_steps = pk // bk

    scalar_spec = pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0))
    in_specs = [
        scalar_spec,                                        # scale
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # A tile
        # packed B blocks hold bk/factor byte rows; blocked indexing keeps
        # byte tiles aligned because factor | bk
        pl.BlockSpec((bk // factor, bn), lambda i, j, kk: (kk, j)),
    ]
    inputs = [scale.reshape(1, 1).astype(jnp.float32), a_codes, b_codes]
    if noise:
        in_specs += [scalar_spec, scalar_spec]              # sigma, seed
        inputs += [jnp.asarray(noise_sigma_acc, jnp.float32).reshape(1, 1),
                   jnp.asarray(noise_seed).astype(jnp.uint32).reshape(1, 1)]

    out_dtype = jnp.int8 if epilogue == "requant" else jnp.float32
    out = pl.pallas_call(
        functools.partial(
            _kernel, k_steps=k_steps, epilogue=epilogue, n_out=n_out, lo=lo,
            noise=noise, mac_chunks=mac_chunks, n_true=n,
            weight_format=weight_format,
        ),
        grid=(pm // bm, pn // bn, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="fq_matmul",
    )(*inputs)
    return out[:m, :n]
