"""Fused implicit-GEMM conv kernel vs the im2col path and lax.conv.

Three oracles, per the FQ-Conv deployment contract:
  * float:   lax.conv_general_dilated on the dequantized codes (dequant
             epilogue) — validates the convolution arithmetic,
  * im2col:  the patches + fq_matmul composition — validates BIT-EXACT
             requant codes (the acceptance bar: both paths produce the
             same int32 accumulators and share the epilogue),
  * stacked: models/kws + models/darknet integer deployment end-to-end
             against the float FQ training path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.fq_conv import fq_conv1d, fq_conv2d, pick_blocks


def _codes(key, shape, lo, hi):
    return jax.random.randint(key, shape, lo, hi + 1).astype(jnp.int8)


# ---------------------------------------------------------------------------
# conv2d: fused vs float conv (dequant epilogue)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", [0, 1])
@pytest.mark.parametrize("dilation", [1, 2])
def test_fused_conv2d_vs_lax_conv(stride, padding, dilation):
    B, H, W, Cin, Cout, ks = 2, 13, 11, 5, 7, 3
    k1, k2 = jax.random.split(jax.random.key(stride * 7 + padding * 3 +
                                             dilation))
    a = _codes(k1, (B, H, W, Cin), 0, 15)
    w = _codes(k2, (ks * ks * Cin, Cout), -7, 7)
    alpha = jnp.float32(0.02)
    got = fq_conv2d(a, w, alpha, kh=ks, kw=ks, stride=(stride, stride),
                    padding=(padding, padding), dilation=(dilation, dilation),
                    epilogue="dequant", interpret=True)
    wf = w.reshape(ks, ks, Cin, Cout).astype(jnp.float32)
    want = jax.lax.conv_general_dilated(
        a.astype(jnp.float32), wf, (stride, stride),
        [(padding, padding), (padding, padding)],
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC")) * alpha
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_conv2d_same_ish_padding_batch1():
    """3x3 stride-1 pad-1 ('SAME') on batch 1, non-multiple-of-128 chans."""
    B, H, W, Cin, Cout, ks = 1, 16, 16, 3, 45, 3
    k1, k2 = jax.random.split(jax.random.key(9))
    a = _codes(k1, (B, H, W, Cin), 0, 15)
    w = _codes(k2, (ks * ks * Cin, Cout), -1, 1)
    alpha = jnp.float32(0.01)
    got = fq_conv2d(a, w, alpha, kh=ks, kw=ks, padding=(1, 1),
                    epilogue="dequant", interpret=True)
    assert got.shape == (B, H, W, Cout)
    wf = w.reshape(ks, ks, Cin, Cout).astype(jnp.float32)
    want = jax.lax.conv_general_dilated(
        a.astype(jnp.float32), wf, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC")) * alpha
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# conv2d: fused requant codes BIT-EXACT vs the im2col path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride,padding,dilation", [
    (1, 0, 1), (1, 1, 1), (2, 0, 1), (2, 1, 1), (1, 1, 2), (2, 2, 2),
])
def test_fused_requant_bitexact_vs_im2col(stride, padding, dilation):
    B, H, W, Cin, Cout, ks = 2, 14, 12, 6, 10, 3
    k1, k2 = jax.random.split(jax.random.key(31 * stride + padding +
                                             5 * dilation))
    a = _codes(k1, (B, H, W, Cin), 0, 15)
    w = _codes(k2, (ks * ks * Cin, Cout), -7, 7)
    scale = jnp.float32(0.013)
    got = ops.fq_conv2d_int(a, w, scale, ksize=ks, stride=stride,
                            padding=padding, dilation=dilation, n_out=15,
                            lo=0, impl="fused")
    want = ops.fq_conv2d_int(a, w, scale, ksize=ks, stride=stride,
                             padding=padding, dilation=dilation, n_out=15,
                             lo=0, impl="im2col")
    assert got.dtype == want.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("cin,cout", [(1, 1), (3, 129), (130, 2)])
def test_fused_awkward_channel_counts(cin, cout):
    """Channel counts far from the 128-lane tile, including Cin=1."""
    B, H, W, ks = 1, 8, 9, 3
    k1, k2 = jax.random.split(jax.random.key(cin * 1000 + cout))
    a = _codes(k1, (B, H, W, cin), 0, 15)
    w = _codes(k2, (ks * ks * cin, cout), -7, 7)
    scale = jnp.float32(0.02)
    got = ops.fq_conv2d_int(a, w, scale, ksize=ks, padding=1, n_out=15,
                            impl="fused")
    want = ops.fq_conv2d_int(a, w, scale, ksize=ks, padding=1, n_out=15,
                             impl="im2col")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_1x1_and_5x5_kernels():
    for ks, pad in [(1, 0), (5, 2)]:
        k1, k2 = jax.random.split(jax.random.key(ks))
        a = _codes(k1, (2, 10, 10, 4), 0, 15)
        w = _codes(k2, (ks * ks * 4, 8), -7, 7)
        scale = jnp.float32(0.01)
        got = ops.fq_conv2d_int(a, w, scale, ksize=ks, padding=pad,
                                n_out=15, impl="fused")
        want = ops.fq_conv2d_int(a, w, scale, ksize=ks, padding=pad,
                                 n_out=15, impl="im2col")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_int32_accumulation():
    """Cin large enough that int8 accumulation would overflow.

    Non-negative codes make every sum ~4e6 (> 2**20) whatever the random
    stream, while 9*512*127*28 < 2**24 keeps the f32 reference exact."""
    k1, k2 = jax.random.split(jax.random.key(3))
    a = _codes(k1, (1, 6, 6, 512), 0, 127)
    w = _codes(k2, (9 * 512, 8), 0, 28)
    got = fq_conv2d(a, w, jnp.float32(1.0), kh=3, kw=3, padding=(1, 1),
                    epilogue="dequant", interpret=True)
    wf = w.reshape(3, 3, 512, 8).astype(jnp.float32)
    want = jax.lax.conv_general_dilated(
        a.astype(jnp.float32), wf, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(jnp.max(jnp.abs(want))) > 2 ** 20  # test is meaningful


def test_block_knobs_dont_change_codes():
    """Explicit (bho, bco, bc) overrides tile differently, same codes."""
    k1, k2 = jax.random.split(jax.random.key(11))
    a = _codes(k1, (2, 12, 12, 8), 0, 15)
    w = _codes(k2, (9 * 8, 12), -7, 7)
    scale = jnp.float32(0.015)
    base = fq_conv2d(a, w, scale, kh=3, kw=3, padding=(1, 1), n_out=15,
                     interpret=True)
    for bho, bco, bc in [(4, 4, 8), (12, 12, 4), (5, 3, 2)]:
        got = fq_conv2d(a, w, scale, kh=3, kw=3, padding=(1, 1), n_out=15,
                        bho=bho, bco=bco, bc=bc, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


@pytest.mark.parametrize("stride,bho", [(1, 3), (1, 4), (2, 2)])
def test_1x1_row_tiles_without_halo(stride, bho):
    """1x1 taps without a pool read no halo: several row tiles (a ragged
    last one included) come from a reshape, not a gather, same codes."""
    k1, k2 = jax.random.split(jax.random.key(17 + stride))
    a = _codes(k1, (2, 10, 9, 16), 0, 15)
    w = _codes(k2, (16, 24), -7, 7)
    scale = jnp.float32(0.02)
    got = fq_conv2d(a, w, scale, kh=1, kw=1, stride=(stride, stride),
                    n_out=15, bho=bho, interpret=True)
    want = ops.fq_conv2d_int(a, w, scale, ksize=1, stride=stride, padding=0,
                             n_out=15, lo=0, impl="im2col")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pick_blocks_respects_divisibility():
    bho, bco, bc = pick_blocks(ho=224, wo=224, cin=96, cout=256, kh=3, kw=3,
                               stride=(1, 1))
    assert 96 % bc == 0 and bho >= 1 and bco <= 256


# ---------------------------------------------------------------------------
# batch-folded grid: B folds into the output-row axis — per-sample results
# must not depend on the serving batch size
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch", [1, 2, 3, 4])
def test_batch_fold_per_sample_invariance(batch):
    ks = 3
    k1, k2 = jax.random.split(jax.random.key(batch))
    a = _codes(k1, (batch, 10, 9, 6), 0, 15)
    w = _codes(k2, (ks * ks * 6, 8), -7, 7)
    scale = jnp.float32(0.02)
    got = fq_conv2d(a, w, scale, kh=ks, kw=ks, padding=(1, 1), n_out=15,
                    interpret=True)
    for i in range(batch):
        one = fq_conv2d(a[i:i + 1], w, scale, kh=ks, kw=ks, padding=(1, 1),
                        n_out=15, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[i:i + 1]),
                                      np.asarray(one), err_msg=f"sample {i}")


# ---------------------------------------------------------------------------
# fused maxpool epilogue: pool on the int32 accumulator in VMEM must be
# bit-exact with the unfused conv + code-domain maxpool composition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride,padding", [
    (1, 0), (1, 1), (2, 0), (2, 1),
])
@pytest.mark.parametrize("hw", [(14, 12), (13, 11)])  # even and odd planes
def test_fused_pool_bitexact_vs_unfused(stride, padding, hw):
    H, W = hw
    B, Cin, Cout, ks = 2, 6, 10, 3
    k1, k2 = jax.random.split(jax.random.key(17 * stride + padding + H))
    a = _codes(k1, (B, H, W, Cin), 0, 15)
    w = _codes(k2, (ks * ks * Cin, Cout), -7, 7)
    scale = jnp.float32(0.013)
    kw = dict(ksize=ks, stride=stride, padding=padding, pool=2, n_out=15,
              lo=0)
    got = ops.fq_conv2d_pool_int(a, w, scale, impl="fused", **kw)
    want = ops.fq_conv2d_pool_int(a, w, scale, impl="im2col", **kw)
    assert got.dtype == want.dtype == jnp.int8
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# fused residual add epilogue: clip(clip(round(acc * r), -n, n) + sc, 0, n)
# in VMEM must be bit-exact with the conv + int_residual_add composition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ks,stride,hw,bho,bco", [
    (1, 1, (14, 12), None, None),   # pitch wq == wo: no spare columns
    (1, 1, (14, 12), 4, 8),         # ragged row tiles, two out blocks
    (1, 2, (13, 11), None, None),   # strided 1x1: one of four phases read
    (3, 1, (14, 12), None, None),   # wq - wo = 2 spare columns
    (3, 1, (13, 11), 5, 8),         # spare columns, ragged rows, out blocks
    (3, 2, (14, 12), None, None),   # wq - wo = 1 spare column
    (3, 2, (13, 11), 3, None),
])
@pytest.mark.parametrize("noise", [False, True])
def test_fused_add_bitexact_vs_oracle(ks, stride, hw, bho, bco, noise):
    H, W = hw
    B, Cin, Cout, n = 2, 6, 16, 7
    k1, k2, k3 = jax.random.split(jax.random.key(7 * ks + stride + H), 3)
    a = _codes(k1, (B, H, W, Cin), 0, n)
    w = _codes(k2, (ks * ks * Cin, Cout), -1, 1)
    pad = ks // 2
    ho, wo = (H + 2 * pad - ks) // stride + 1, (W + 2 * pad - ks) // stride + 1
    sc = _codes(k3, (B, ho, wo, Cout), 0, n)
    scale = jnp.float32(0.21)
    nkw = (dict(noise_sigma_acc=jnp.float32(2.0), noise_seed=jnp.uint32(9))
           if noise else {})
    want = ops.fq_conv2d_add_int(a, w, scale, sc, ksize=ks, stride=stride,
                                 padding=pad, n_out=n, lo=-n, impl="im2col",
                                 **nkw)
    got = fq_conv2d(a, w, scale, kh=ks, kw=ks, stride=(stride, stride),
                    padding=(pad, pad), n_out=n, lo=-n, bho=bho, bco=bco,
                    residual=sc, interpret=True, **nkw)
    assert got.dtype == want.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    g = np.asarray(got)
    assert g.min() == 0 and g.max() == n  # both clips are exercised
    # the add is the oracle's own: branch codes plus shortcut, clipped
    branch = ops.fq_conv2d_int(a, w, scale, ksize=ks, stride=stride,
                               padding=pad, n_out=n, lo=-n, impl="im2col",
                               **nkw)
    np.testing.assert_array_equal(
        g, np.clip(np.asarray(branch, np.int32) + np.asarray(sc), 0, n))


def test_fused_pool_matches_separate_maxpool_op():
    """fq_conv2d(pool=) == int_maxpool2d(fq_conv2d()) — the commuting-max
    claim, checked against the production code-domain pool itself."""
    from repro.core import integer_inference as ii
    k1, k2 = jax.random.split(jax.random.key(23))
    a = _codes(k1, (3, 12, 12, 4), 0, 15)
    w = _codes(k2, (9 * 4, 9), -7, 7)
    scale = jnp.float32(0.02)
    unpooled = fq_conv2d(a, w, scale, kh=3, kw=3, padding=(1, 1), n_out=15,
                         interpret=True)
    want = ii.int_maxpool2d(unpooled)
    got = fq_conv2d(a, w, scale, kh=3, kw=3, padding=(1, 1), pool=(2, 2),
                    n_out=15, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_pool_dequant_epilogue():
    """Pool also commutes with the (positive-scale) dequant epilogue."""
    k1, k2 = jax.random.split(jax.random.key(5))
    a = _codes(k1, (2, 10, 9, 4), 0, 15)
    w = _codes(k2, (9 * 4, 6), -7, 7)
    alpha = jnp.float32(0.02)
    got = fq_conv2d(a, w, alpha, kh=3, kw=3, padding=(1, 1), pool=(2, 2),
                    epilogue="dequant", interpret=True)
    unpooled = fq_conv2d(a, w, alpha, kh=3, kw=3, padding=(1, 1),
                         epilogue="dequant", interpret=True)
    want = ops.maxpool2d(unpooled)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_fused_pool_block_knobs_dont_change_codes():
    """Odd explicit bho is rounded to the pool height; codes unchanged."""
    k1, k2 = jax.random.split(jax.random.key(29))
    a = _codes(k1, (2, 12, 12, 8), 0, 15)
    w = _codes(k2, (9 * 8, 12), -7, 7)
    scale = jnp.float32(0.015)
    base = fq_conv2d(a, w, scale, kh=3, kw=3, padding=(1, 1), pool=(2, 2),
                     n_out=15, interpret=True)
    for bho, bco, bc in [(5, 3, 2), (4, 4, 8), (12, 12, 4), (2, 128, 8)]:
        got = fq_conv2d(a, w, scale, kh=3, kw=3, padding=(1, 1), pool=(2, 2),
                        n_out=15, bho=bho, bco=bco, bc=bc, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_pick_blocks_pool_rounds_bho():
    bho, _, _ = pick_blocks(ho=17, wo=17, cin=8, cout=16, kh=3, kw=3,
                            stride=(1, 1), pool=(2, 2), bho=5)
    assert bho == 4
    bho, _, _ = pick_blocks(ho=17, wo=17, cin=8, cout=16, kh=3, kw=3,
                            stride=(1, 1), pool=(2, 2), bho=1)
    assert bho == 2  # never below the pool height


# ---------------------------------------------------------------------------
# zero-sigma noise plumbing: every noise entry point, disabled, must be
# BIT-EXACT vs the clean path — across the same stride/padding/pool parity
# sweep the clean guarantees are proven on
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["fused", "im2col"])
@pytest.mark.parametrize("stride,padding", [(1, 0), (1, 1), (2, 0), (2, 1)])
@pytest.mark.parametrize("pool", [None, 2])
def test_zero_sigma_conv2d_bitexact(impl, stride, padding, pool):
    """noise kwargs at their disabled defaults (None / chunks=1) leave the
    conv dispatch point byte-identical to the clean path."""
    B, H, W, Cin, Cout, ks = 2, 14, 12, 6, 10, 3
    k1, k2 = jax.random.split(jax.random.key(41 * stride + padding))
    a = _codes(k1, (B, H, W, Cin), 0, 15)
    w = _codes(k2, (ks * ks * Cin, Cout), -7, 7)
    scale = jnp.float32(0.013)
    kw = dict(ksize=ks, stride=stride, padding=padding, n_out=15, lo=0,
              impl=impl)
    if pool is None:
        clean = ops.fq_conv2d_int(a, w, scale, **kw)
        got = ops.fq_conv2d_int(a, w, scale, noise_sigma_acc=None,
                                noise_seed=None, mac_chunks=1, **kw)
    else:
        clean = ops.fq_conv2d_pool_int(a, w, scale, pool=pool, **kw)
        got = ops.fq_conv2d_pool_int(a, w, scale, pool=pool,
                                     noise_sigma_acc=None, noise_seed=None,
                                     mac_chunks=1, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("impl", ["fused", "im2col"])
def test_zero_sigma_stacks_bitexact(impl):
    """kws/darknet int_apply with noise=None AND NoiseConfig(0,0,0)+rng
    both reproduce the clean integer stack bit-for-bit (the batched-vs-
    unbatched and fused-vs-im2col guarantees ride on the clean suite)."""
    from conftest import trained_int_params
    from repro.core.noise import NoiseConfig
    from repro.core.quant import QuantConfig
    from repro.models import darknet, kws
    qcfg = QuantConfig(2, 4, 4, fq=True)
    zero = NoiseConfig(0.0, 0.0, 0.0)

    cfg = kws.KWSConfig.reduced()
    _, _, ip = trained_int_params(
        kws, cfg, [f"conv{i}" for i in range(len(cfg.dilations))], qcfg)
    x = jax.random.normal(jax.random.key(1), (3, cfg.seq_len, cfg.n_mfcc))
    clean = kws.int_apply(ip, x, qcfg, cfg, impl=impl)
    for noise, rng in [(None, None), (zero, jax.random.key(2))]:
        got = kws.int_apply(ip, x, qcfg, cfg, impl=impl, noise=noise,
                            rng=rng)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))

    dcfg = darknet.DarkNetConfig.reduced()
    names = [f"conv{i}" for i in
             range(len([l for l in dcfg.layers if l != "M"]))]
    _, _, dip = trained_int_params(darknet, dcfg, names, qcfg, s_out=0.2)
    xd = jax.random.normal(jax.random.key(3), (2, 16, 16, dcfg.in_channels))
    for fuse_pool in (False, True):
        clean = darknet.int_apply(dip, xd, qcfg, dcfg, impl=impl,
                                  fuse_pool=fuse_pool)
        for noise, rng in [(None, None), (zero, jax.random.key(4))]:
            got = darknet.int_apply(dip, xd, qcfg, dcfg, impl=impl,
                                    fuse_pool=fuse_pool, noise=noise,
                                    rng=rng)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


def test_zero_sigma_matmul_bitexact():
    from repro.kernels.fq_matmul import fq_matmul
    k1, k2 = jax.random.split(jax.random.key(6))
    a = _codes(k1, (33, 40), 0, 15)
    b = _codes(k2, (40, 21), -7, 7)
    scale = jnp.float32(0.02)
    clean = fq_matmul(a, b, scale, n_out=15, interpret=True)
    got = fq_matmul(a, b, scale, n_out=15, noise_sigma_acc=None,
                    noise_seed=None, mac_chunks=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


# ---------------------------------------------------------------------------
# int_maxpool2d on odd planes (VALID semantics: trailing row/col dropped)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hw", [(5, 7), (9, 5), (6, 6), (7, 1), (1, 4)])
def test_int_maxpool2d_odd_hw(hw):
    from repro.core import integer_inference as ii
    H, W = hw
    codes = _codes(jax.random.key(H * 10 + W), (2, H, W, 3), -8, 7)
    got = ii.int_maxpool2d(codes)
    assert got.dtype == jnp.int8
    assert got.shape == (2, H // 2, W // 2, 3)
    want = jax.lax.reduce_window(
        codes.astype(jnp.float32), -jnp.inf, jax.lax.max,
        (1, 2, 2, 1), (1, 2, 2, 1), "VALID").astype(jnp.int8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# autotune table loading
# ---------------------------------------------------------------------------


def test_autotune_table_loads_matching_backend(tmp_path):
    from repro.kernels import fq_conv as fc
    doc = {"format": 1, "backend": jax.default_backend(),
           "entries": [{"kh": 3, "kw": 3, "stride": 1,
                        "bho": 16, "bco": 64, "bc": 8}]}
    p = tmp_path / "table.json"
    p.write_text(__import__("json").dumps(doc))
    table = fc.load_autotune_table(str(p))
    assert table[(3, 3, 1, "int8")] == {"bho": 16, "bco": 64, "bc": 8}
    # other-backend entries are ignored -> builtin defaults survive
    doc["backend"] = "not-a-backend"
    p.write_text(__import__("json").dumps(doc))
    table = fc.load_autotune_table(str(p))
    assert table[(3, 3, 1, "int8")] == fc._BUILTIN_TABLE[(3, 3, 1, "int8")]
    # missing/corrupt file -> builtin defaults
    table = fc.load_autotune_table(str(tmp_path / "nope.json"))
    assert table[(1, 1, 1, "int8")] == fc._BUILTIN_TABLE[(1, 1, 1, "int8")]


# ---------------------------------------------------------------------------
# conv1d: fused vs im2col, all KWS dilations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dil", [1, 2, 4, 8])
def test_fused_conv1d_bitexact_vs_im2col(dil):
    B, T, Cin, Cout, ks = 2, 40, 8, 8, 3
    k1, k2 = jax.random.split(jax.random.key(dil))
    a = _codes(k1, (B, T, Cin), 0, 15)
    w = _codes(k2, (ks * Cin, Cout), -1, 1)
    scale = jnp.float32(0.01)
    got = ops.fq_conv1d_int(a, w, scale, ksize=ks, dilation=dil, n_out=15,
                            impl="fused")
    want = ops.fq_conv1d_int(a, w, scale, ksize=ks, dilation=dil, n_out=15,
                             impl="im2col")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_conv1d_batch1_dequant():
    a = _codes(jax.random.key(0), (1, 24, 5), 0, 15)
    w = _codes(jax.random.key(1), (3 * 5, 9), -7, 7)
    alpha = jnp.float32(0.03)
    got = fq_conv1d(a, w, alpha, ksize=3, dilation=2, epilogue="dequant",
                    interpret=True)
    wf = w.reshape(3, 5, 9).astype(jnp.float32)
    want = jax.lax.conv_general_dilated(
        a.astype(jnp.float32), wf, (1,), "VALID", rhs_dilation=(2,),
        dimension_numbers=("NTC", "TIO", "NTC")) * alpha
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# dispatch point
# ---------------------------------------------------------------------------


def test_conv_dispatch_auto_and_override():
    assert ops.conv_impl(None) in ("fused", "im2col")
    assert ops.conv_impl("fused") == "fused"
    ops.set_conv_impl("fused")
    try:
        assert ops.conv_impl(None) == "fused"
        assert ops.conv_impl("im2col") == "im2col"  # explicit wins
    finally:
        ops.set_conv_impl(None)


# ---------------------------------------------------------------------------
# integer model stacks: fused kernel end-to-end vs the float FQ path
# ---------------------------------------------------------------------------


def _chain_scales(params, names):
    """Enforce the FQ hand-off contract s_in[i+1] == s_out[i] in-place."""
    for a, b in zip(names, names[1:]):
        params[b]["s_in"] = params[a]["s_out"]
    return params


@pytest.mark.parametrize("impl", ["im2col", "fused"])
def test_kws_int_apply_bit_exact(impl):
    from repro.core.quant import QuantConfig
    from repro.models import kws
    cfg = kws.KWSConfig.reduced()
    qcfg = QuantConfig(2, 4, 4, fq=True)
    params, state = kws.init(jax.random.key(0), cfg)
    params = kws.to_fq(params, state, cfg)
    names = [f"conv{i}" for i in range(len(cfg.dilations))]
    for n in names:  # trained-like scales in a sane range
        params[n]["s_out"] = jnp.float32(0.1)
    _chain_scales(params, names)
    x = jax.random.normal(jax.random.key(1), (3, cfg.seq_len, cfg.n_mfcc))

    y_float, _ = kws.apply(params, state, x, qcfg, cfg, train=False)
    ip = kws.convert_int(params, state, qcfg, cfg)
    y_int = kws.int_apply(ip, x, qcfg, cfg, impl=impl)
    np.testing.assert_allclose(np.asarray(y_float), np.asarray(y_int),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("impl", ["im2col", "fused"])
@pytest.mark.parametrize("fuse_pool", [False, True])
def test_darknet_int_apply_bit_exact(impl, fuse_pool):
    """conv+pool pairs through int_conv2d_pool (fuse_pool=True) must match
    both the conv-then-pool composition and the float FQ path."""
    from repro.core.quant import QuantConfig
    from repro.models import darknet
    cfg = darknet.DarkNetConfig.reduced()
    qcfg = QuantConfig(2, 4, 4, fq=True)
    params, state = darknet.init(jax.random.key(0), cfg)
    params = darknet.to_fq(params, state, cfg)
    convs = [l for l in cfg.layers if l != "M"]
    names = [f"conv{i}" for i in range(len(convs))]
    for n in names:
        params[n]["s_out"] = jnp.float32(0.2)
    _chain_scales(params, names)
    x = jax.random.normal(jax.random.key(1), (2, 16, 16, cfg.in_channels))

    y_float, _ = darknet.apply(params, state, x, qcfg, cfg, train=False)
    ip = darknet.convert_int(params, state, qcfg, cfg)
    y_int = darknet.int_apply(ip, x, qcfg, cfg, impl=impl,
                              fuse_pool=fuse_pool)
    np.testing.assert_allclose(np.asarray(y_float), np.asarray(y_int),
                               rtol=0, atol=1e-5)
    # fused and unfused pool routing are bit-identical, not just close
    y_ref = darknet.int_apply(ip, x, qcfg, cfg, impl=impl, fuse_pool=False)
    np.testing.assert_array_equal(np.asarray(y_int), np.asarray(y_ref))
