"""Noise injection on weights / activations / MAC results (paper §4.4).

Models analog-accelerator non-idealities: noisy memory cells (weights), DACs
(activations) and ADCs (MAC results). Noise is Gaussian with sigma expressed
as a *percentage of one LSB* — one quantization interval, e^s / n — exactly
the paper's parameterization, so Table 7's (sigma_w, sigma_a, sigma_MAC)
triples map 1:1 onto :class:`NoiseConfig`.

Two noise domains live here:

  * **Float FQ training path** (:func:`add_lsb_noise`) — Gaussian on the
    dequantized tensors, keyed by jax PRNG keys (noise-aware training,
    Table 7's "trained with noise" rows).
  * **Integer deployment path** — the code-domain / accumulator-domain
    model the integer stacks and the Pallas kernels share:
      - :func:`perturb_codes` draws Gaussian noise in *code units* (sigma
        in fractions of an LSB IS the code-unit std, since one code step
        is one LSB), rounds back to integers and clips to the quantizer
        range — the DAC / memory-cell noise of the analog design,
      - :func:`mac_noise_field` is a *deterministic counter-hash* Gaussian
        field over global output-element indices, evaluated with identical
        elementwise jnp ops inside the fused Pallas kernel epilogue and on
        the im2col reference path, so the in-kernel ADC noise is
        reproducible bit-for-bit by the oracle. ``chunks`` models the
        paper's chunked-accumulation mitigation: the reduction is read out
        by K per-chunk ADC conversions, each spanning 1/K of the dynamic
        range (K-times-finer LSB), so each chunk draw has std sigma/K and
        the summed noise std is sigma/sqrt(K).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .quant import lsb


@dataclasses.dataclass(frozen=True)
class NoiseConfig:
    """sigma_* as fractions of one LSB (paper's % / 100)."""

    sigma_w: float = 0.0
    sigma_a: float = 0.0
    sigma_mac: float = 0.0

    @property
    def enabled(self) -> bool:
        return self.sigma_w > 0 or self.sigma_a > 0 or self.sigma_mac > 0


# Table 7's five test conditions, (sigma_w, sigma_a, sigma_mac) in % LSB.
TABLE7_CONDITIONS = [
    NoiseConfig(0.01, 0.01, 0.05),
    NoiseConfig(0.05, 0.05, 0.25),
    NoiseConfig(0.10, 0.10, 0.50),
    NoiseConfig(0.20, 0.20, 1.00),
    NoiseConfig(0.30, 0.30, 1.50),
]


def add_lsb_noise(
    x: jax.Array,
    key: Optional[jax.Array],
    sigma: float,
    s: jax.Array,
    bits: Optional[int],
) -> jax.Array:
    """x + N(0, sigma * LSB) where LSB = e^s / n for the given quantizer.

    No-op when sigma == 0, key is None, or the tensor is full precision
    (bits is None — then there is no LSB to scale by).
    """
    if sigma <= 0.0 or key is None or bits is None:
        return x
    step = lsb(s, bits).astype(x.dtype)
    return x + sigma * step * jax.random.normal(key, x.shape, x.dtype)


# ---------------------------------------------------------------------------
# Integer-path noise: code-domain perturbation (weights / activations)
# ---------------------------------------------------------------------------


def perturb_codes(codes: jax.Array, key: Optional[jax.Array], sigma: float,
                  *, lo: int, hi: int) -> jax.Array:
    """Code-domain Gaussian noise: round(codes + sigma * g), clipped.

    One code step IS one LSB, so the paper's sigma (fraction of an LSB)
    is directly the std in code units — no scale parameter needed. The
    result stays an integer code in [lo, hi] (the quantizer's range):
    analog cell/DAC noise below half a code step rounds away, exactly as
    the re-digitized value would on hardware. No-op when sigma == 0 or
    key is None, so the clean path never pays a PRNG draw.
    """
    if sigma <= 0.0 or key is None:
        return codes
    g = jax.random.normal(key, codes.shape, jnp.float32)
    y = jnp.round(codes.astype(jnp.float32) + sigma * g)
    return jnp.clip(y, lo, hi).astype(codes.dtype)


def derive_seed(key: jax.Array) -> jax.Array:
    """Fold a jax PRNG key into the uint32 seed the kernel noise field
    takes — the host side of the per-layer key split."""
    return jax.random.bits(key, (), jnp.uint32)


# ---------------------------------------------------------------------------
# Integer-path noise: deterministic accumulator ("ADC") noise field
# ---------------------------------------------------------------------------
# The MAC noise must be drawn *inside* the fused kernel's VMEM epilogue yet
# be reproducible bit-for-bit by the im2col + fq_matmul reference, under any
# tile shape. A stateful hardware PRNG (pltpu.prng_seed) cannot satisfy
# that — its stream depends on the grid walk — so the field is a stateless
# counter hash over the GLOBAL output-element index: both paths evaluate the
# same elementwise uint32/f32 expressions on the same indices and therefore
# produce identical bits (ROADMAP notes the pltpu.prng_seed follow-up).


def hash_u32(x: jax.Array) -> jax.Array:
    """Avalanche mix on uint32 (splitmix/murmur3-finalizer family).

    Pure elementwise ops — shifts, xors, wrapping multiplies — so it
    traces identically inside Pallas kernel bodies (interpret and Mosaic)
    and in plain jnp reference code.
    """
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


_GOLDEN = 0x9E3779B9   # 2^32 / phi — the classic odd salt constant
_IH_DRAWS = 12         # Irwin-Hall(12): sum of 12 U(0,1) has variance 1


def unit_normal_field(idx: jax.Array, seed: jax.Array,
                      salt: int = 0) -> jax.Array:
    """Deterministic ~N(0, 1) per element of ``idx`` (int32/uint32 indices).

    Irwin-Hall(12): twelve hashed 24-bit uniforms summed, minus 6 — exact
    unit variance, support [-6, 6], and only integer hashes + f32 adds, so
    it runs unchanged inside a Pallas kernel body.
    """
    seed = jnp.asarray(seed).astype(jnp.uint32)
    base = hash_u32(idx.astype(jnp.uint32)
                    ^ hash_u32(seed + jnp.uint32((salt * _GOLDEN) & 0xFFFFFFFF)))
    u_sum = jnp.zeros(idx.shape, jnp.float32)
    for k in range(_IH_DRAWS):
        h = hash_u32(base + jnp.uint32(((k + 1) * _GOLDEN) & 0xFFFFFFFF))
        # 24-bit value: exact through int32, and Mosaic has no direct
        # uint32 -> f32 cast
        u_sum = u_sum + (h >> 8).astype(jnp.int32).astype(jnp.float32)
    return u_sum * jnp.float32(2.0 ** -24) - jnp.float32(_IH_DRAWS / 2)


def mac_noise_field(idx: jax.Array, seed: jax.Array, sigma: jax.Array,
                    *, chunks: int = 1) -> jax.Array:
    """ADC noise for the int32 MAC accumulator, in accumulator units.

    ``sigma`` is the per-conversion std in accumulator units (the caller
    folds the paper's sigma_mac * LSB through the requant scale:
    sigma_acc = sigma_mac / rescale). ``chunks=K`` models the paper's
    chunked-accumulation mitigation: the reduction is converted by K
    per-chunk ADCs, each spanning 1/K of the dynamic range so each draw
    has std sigma/K; the K draws sum to an effective std of
    sigma/sqrt(K). chunks=1 is the plain single-ADC model. The chunk
    draws are data-independent and additive, so applying their sum in
    the epilogue is exactly the per-chunk-boundary application.
    """
    assert chunks >= 1
    total = unit_normal_field(idx, seed, salt=0)
    for c in range(1, chunks):
        total = total + unit_normal_field(idx, seed, salt=c)
    return jnp.asarray(sigma).astype(jnp.float32) / chunks * total
