"""Pallas TPU kernel: learned quantization (paper eq. 1+2) to int8 codes.

Elementwise  codes = round(clip(x / e^s, b, 1) * n)  streamed through VMEM in
row tiles. Used on the inference path to quantize network inputs and any
tensor entering an FQ layer from a full-precision producer; inside the FQ
stack the matmul epilogue produces codes directly so no separate pass is paid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(inv_scale_ref, x_ref, o_ref, *, n: int, b: float):
    x = x_ref[...].astype(jnp.float32) * inv_scale_ref[0, 0]
    o_ref[...] = jnp.round(jnp.clip(x, b, 1.0) * n).astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("n", "b", "block_rows", "interpret")
)
def quantize_codes(
    x: jax.Array,          # (R, C) float
    inv_scale: jax.Array,  # scalar f32 = e^{-s}
    *,
    n: int,
    b: float,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    r, c = x.shape
    rp = -r % block_rows
    if rp:
        x = jnp.pad(x, ((0, rp), (0, 0)))
    pr = r + rp
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, b=b),
        grid=(pr // block_rows,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pr, c), jnp.int8),
        interpret=interpret,
        name="quantize_codes",
    )(inv_scale.reshape(1, 1).astype(jnp.float32), x)
    return out[:r]
