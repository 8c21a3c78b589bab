"""Compile the serving path's kernels for a described TPU v5e, no chip.

Mosaic refuses what interpret mode accepts (block shapes off the (8, 128)
tiling, unaligned dynamic slices, casts the TPU lacks), so these compiles
guard every change to the kernels at real widths: the fused conv at
DarkNet-19 and KWS layer shapes, ``fq_matmul`` and ``quantize_codes``.
Each asserts that the compiled HLO holds the Mosaic kernel.

The topology is described inside a module fixture (never at import), and
every test here stays in this one file: only the worker that runs them
loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import fq_conv
from repro.kernels.fq_matmul import fq_matmul
from repro.kernels.quantize import quantize_codes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def chip_blocks(monkeypatch):
    """The block picks the chip makes: the checked-in table holds
    CPU-interpret winners, which the TPU loader ignores."""
    monkeypatch.setattr(fq_conv, "AUTOTUNE_TABLE",
                        fq_conv.load_autotune_table(os.devnull))
    monkeypatch.setattr(fq_conv, "MEASURED_KEYS", set())


def _mosaic_hlo(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("hw,cin,cout,k,pool,batch", [
    (112, 32, 64, 3, True, 8),     # DarkNet-19 conv1 + fused 2x2 pool
    (56, 128, 64, 1, False, 8),    # 56² 1x1: row tiles without halo
    (28, 128, 256, 3, False, 1),   # 28² 3x3
    (14, 512, 256, 1, False, 8),   # 14² 1x1
    (7, 512, 1024, 3, False, 1),   # 7² 3x3
])
def test_fq_conv2d_compiles(one_chip, chip_blocks, hw, cin, cout, k, pool,
                            batch):
    hlo = _mosaic_hlo(
        lambda a, w, s: fq_conv.fq_conv2d(
            a, w, s, kh=k, kw=k, padding=(k // 2, k // 2),
            pool=(2, 2) if pool else None),
        one_chip, ((batch, hw, hw, cin), jnp.int8),
        ((k * k * cin, cout), jnp.int8), ((), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("hw,cin,cout,k,stride,add", [
    (56, 128, 128, 3, 2, False),   # ResNet-50 s1b0_c2: stride-2 3x3
    (28, 256, 256, 3, 2, False),   # s2b0_c2
    (14, 512, 512, 3, 2, False),   # s3b0_c2
    (56, 256, 512, 1, 2, False),   # s1b0_sc: stride-2 1x1 projection
    (28, 512, 1024, 1, 2, False),  # s2b0_sc
    (14, 1024, 2048, 1, 2, False),  # s3b0_sc
    (56, 64, 256, 1, 1, True),     # s0 blocks' last conv + residual add
    (28, 128, 512, 1, 1, True),    # s1
    (14, 256, 1024, 1, 1, True),   # s2
    (7, 512, 2048, 1, 1, True),    # s3
])
def test_fq_conv2d_resnet50_compiles(one_chip, chip_blocks, hw, cin, cout, k,
                                     stride, add):
    """ResNet-50's strided convs and its fused conv + add, at batch 8."""
    ho = -(-hw // stride)
    shapes = [((8, hw, hw, cin), jnp.int8), ((k * k * cin, cout), jnp.int8),
              ((), jnp.float32)]
    if add:
        shapes.append(((8, ho, ho, cout), jnp.int8))

    def conv(a, w, s, *res):
        return fq_conv.fq_conv2d(a, w, s, kh=k, kw=k, stride=(stride, stride),
                                 padding=(k // 2, k // 2), lo=-7,
                                 residual=res[0] if res else None)

    hlo = _mosaic_hlo(conv, one_chip, *shapes)
    assert "tpu_custom_call" in hlo
    assert ("fq_conv2d_add" in hlo) == add


@pytest.mark.parametrize("weight_format,noise", [
    ("int4", False), ("ternary", False), ("int8", True), ("ternary", True),
])
def test_fq_conv2d_packed_and_noise_compile(one_chip, chip_blocks,
                                            weight_format, noise):
    """28² 3x3 128->256 with packed weights (unpacked in VMEM ahead of the
    MAC) and with the ADC-noise epilogue (its hash casts through int32:
    Mosaic has no uint32 -> f32)."""
    from repro.core import quant
    rows = 9 * 128 // quant.format_factor(weight_format)
    w_dtype = jnp.int8 if weight_format == "int8" else jnp.uint8
    shapes = [((1, 28, 28, 128), jnp.int8), ((rows, 256), w_dtype),
              ((), jnp.float32)]
    if noise:
        shapes += [((), jnp.float32), ((), jnp.uint32)]

    def conv(a, w, s, *noise_args):
        sigma, seed = noise_args or (None, None)
        return fq_conv.fq_conv2d(a, w, s, kh=3, kw=3, padding=(1, 1),
                                 noise_sigma_acc=sigma, noise_seed=seed,
                                 weight_format=weight_format)

    hlo = _mosaic_hlo(conv, one_chip, *shapes)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("t,cin,dil", [
    (140, 100, 1), (136, 45, 2), (76, 45, 32),   # KWS @140 frames
])
def test_fq_conv1d_kws_compiles(one_chip, chip_blocks, t, cin, dil):
    hlo = _mosaic_hlo(
        lambda a, w, s: fq_conv.fq_conv1d(a, w, s, ksize=3, dilation=dil),
        one_chip, ((4, t, cin), jnp.int8), ((3 * cin, 45), jnp.int8),
        ((), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_fq_matmul_compiles(one_chip):
    hlo = _mosaic_hlo(lambda a, b, s: fq_matmul(a, b, s), one_chip,
                      ((512, 1152), jnp.int8), ((1152, 256), jnp.int8),
                      ((), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows,cols", [(50176, 32), (560, 100)])
def test_quantize_codes_compiles(one_chip, rows, cols):
    """DarkNet-19's entry plane (8 x 112² x 32) and KWS's (4 x 140 x 100)."""
    hlo = _mosaic_hlo(lambda x, s: quantize_codes(x, s, n=15, b=0.0),
                      one_chip, ((rows, cols), jnp.float32),
                      ((), jnp.float32))
    assert "tpu_custom_call" in hlo
