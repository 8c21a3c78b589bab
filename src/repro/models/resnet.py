"""ResNets: the paper's CIFAR nets (§4.1 ResNet-20, §4.3 ResNet-32 /
Figure 4) and ImageNet ResNet-50 v1.5 (He et al. 2016, Table 1; the
stride on the 3x3 conv of each downsampling block, as in MLPerf
Inference's reference model).

Pre-FQ mode (Fig 4A): conv -> BN -> ReLU -> conv -> BN, +shortcut, ReLU.
FQ mode (Fig 4B): BN+ReLU -> quantized ReLU (b=0); isolated BN -> learned
quantization with b=-1; the residual add stays higher precision (like the
paper's pooling/softmax). 1x1 downsample convs in the shortcut are quantized
too; the input image is quantized by the first conv's input quantizer.
The float ``init``/``apply``/``to_fq`` cover the CIFAR (basic-block) nets.

Integer deployment (bottom of the file) serves both block kinds: the stem
and the classifier stay float (§4.1's ImageNet protocol) and every block
runs in codes, the residual add included — a departure from Fig 4B: the
residual stream is integer, each stage's stream on one common scale
(requant-to-common-scale, docs/TRANSFORMER.md), and the add runs in the
block's last conv's epilogue.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import fq_layers as fql
from ..core.noise import NoiseConfig
from ..core.quant import QuantConfig, RELU_BOUND, WEIGHT_BOUND, n_levels

# stem kind -> (conv ksize, conv stride, max pool (window, stride) or None)
STEMS = {"cifar": (3, 1, None), "imagenet": (7, 2, (3, 2))}


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    widths: Tuple[int, ...] = (16, 32, 64)       # ResNet-20 (CIFAR-10)
    blocks_per_stage: Union[int, Tuple[int, ...]] = 3  # one int: every stage
    num_classes: int = 10
    quantize_first_last: bool = True             # paper §4.1 uses False
    block: str = "basic"        # "basic": 3x3, 3x3 | "bottleneck": 1x1, 3x3, 1x1
    expansion: int = 1          # a block's output channels: width * expansion
    stem: str = "cifar"         # a key of STEMS
    in_channels: int = 3

    @property
    def stage_blocks(self) -> Tuple[int, ...]:
        if isinstance(self.blocks_per_stage, int):
            return (self.blocks_per_stage,) * len(self.widths)
        return tuple(self.blocks_per_stage)

    @classmethod
    def resnet20(cls, quantize_first_last=False):
        return cls((16, 32, 64), 3, 10, quantize_first_last)

    @classmethod
    def resnet32(cls):
        # Paper Fig 4: 3 ResBlocks of five subblocks, widths 64 -> 256.
        return cls((64, 128, 256), 5, 100, True)

    @classmethod
    def resnet50(cls):
        """ResNet-50 v1.5 for ImageNet: 7x7/2 stem and 3x3/2 max pool,
        [3, 4, 6, 3] bottleneck blocks at widths 64..512 (expansion 4),
        global average pool, 2048 -> 1000 classifier."""
        return cls((64, 128, 256, 512), (3, 4, 6, 3), 1000, False,
                   "bottleneck", 4, "imagenet")

    @classmethod
    def reduced(cls):
        return cls((8, 16), 1, 10, True)

    @classmethod
    def reduced_bottleneck(cls):
        """ResNet-50's structure at CPU-test size: ImageNet stem, a stage
        of a projection and an identity block, a stride-2 stage."""
        return cls((8, 16), (2, 1), 10, False, "bottleneck", 4, "imagenet")


def init(key, cfg: ResNetConfig):
    params, state = {}, {}
    k = iter(jax.random.split(key, 4 + 6 * len(cfg.widths) * cfg.blocks_per_stage))

    def bn(name, c):
        p, s = fql.init_batchnorm(c)
        params[name + "_bn"], state[name + "_bn"] = p, s

    params["stem"] = fql.init_fq_conv2d(next(k), 3, 3, cfg.widths[0])
    bn("stem", cfg.widths[0])
    cin = cfg.widths[0]
    for si, w in enumerate(cfg.widths):
        for bi in range(cfg.blocks_per_stage):
            pre = f"s{si}b{bi}"
            params[pre + "_c1"] = fql.init_fq_conv2d(next(k), 3, cin, w)
            bn(pre + "_c1", w)
            params[pre + "_c2"] = fql.init_fq_conv2d(next(k), 3, w, w)
            bn(pre + "_c2", w)
            if cin != w:  # downsample shortcut: 1x1 conv + BN (quantized too)
                params[pre + "_sc"] = fql.init_fq_conv2d(next(k), 1, cin, w)
                bn(pre + "_sc", w)
            cin = w
    params["head"] = fql.init_dense(next(k), cin, cfg.num_classes)
    return params, state


def _maybe_fp(qcfg: QuantConfig, quantize: bool) -> QuantConfig:
    return qcfg if quantize else QuantConfig(fq=qcfg.fq)


def apply(params, state, x, qcfg: QuantConfig, cfg: ResNetConfig, *,
          train: bool = False, rng=None,
          noise: Optional[NoiseConfig] = None):
    """x: (B, 32, 32, 3) images in [-1, 1] -> logits."""
    new_state = dict(state)
    n_layers = 1 + 3 * len(cfg.widths) * cfg.blocks_per_stage
    rngs = iter(jax.random.split(rng, n_layers)) if rng is not None else None

    def nxt():
        return next(rngs) if rngs is not None else None

    def conv_bn(name, h, lq, *, stride=1, relu=True, b_in=WEIGHT_BOUND):
        h = fql.fq_conv2d(params[name], h, lq, stride=stride, padding="SAME",
                          b_in=b_in, relu_out=relu, noise=noise, rng=nxt())
        if not lq.fq:
            h, new_state[name + "_bn"] = fql.batchnorm(
                params[name + "_bn"], state[name + "_bn"], h, train=train)
            if relu:
                h = jax.nn.relu(h)
        return h

    stem_q = _maybe_fp(qcfg, cfg.quantize_first_last)
    # Input images quantized by the stem's input quantizer (b=-1, §4.3).
    h = conv_bn("stem", x, stem_q, b_in=WEIGHT_BOUND)
    cin = cfg.widths[0]
    for si, w in enumerate(cfg.widths):
        for bi in range(cfg.blocks_per_stage):
            pre = f"s{si}b{bi}"
            stride = 2 if (cin != w) else 1
            shortcut = h
            h1 = conv_bn(pre + "_c1", h, qcfg, stride=stride, relu=True,
                         b_in=RELU_BOUND)
            # Second conv: isolated BN (no ReLU) -> FQ uses b=-1 quantizer.
            h2 = fql.fq_conv2d(params[pre + "_c2"], h1, qcfg, padding="SAME",
                               b_in=RELU_BOUND, relu_out=False, noise=noise,
                               rng=nxt())
            if not qcfg.fq:
                h2, new_state[pre + "_c2_bn"] = fql.batchnorm(
                    params[pre + "_c2_bn"], state[pre + "_c2_bn"], h2,
                    train=train)
            if pre + "_sc" in params:
                shortcut = fql.fq_conv2d(
                    params[pre + "_sc"], shortcut, qcfg, stride=stride,
                    padding="SAME", b_in=RELU_BOUND, relu_out=False,
                    noise=noise, rng=nxt())
                if not qcfg.fq:
                    shortcut, new_state[pre + "_sc_bn"] = fql.batchnorm(
                        params[pre + "_sc_bn"], state[pre + "_sc_bn"],
                        shortcut, train=train)
            h = jax.nn.relu(h2 + shortcut)  # FP add + ReLU between blocks
            cin = w
    h = jnp.mean(h, axis=(1, 2))  # FP global average pool
    return fql.dense(params["head"], h), new_state


def to_fq(params, state, cfg: ResNetConfig):
    """Fold every BN into its conv for FQ retraining (paper §3.4/Fig 4B)."""
    new = dict(params)
    for name in list(params):
        if name + "_bn" in params:
            new[name] = fql.fold_bn(params[name], params[name + "_bn"],
                                    state[name + "_bn"])
    return new


# ---------------------------------------------------------------------------
# Integer deployment (paper §3.4) over the residual DAG. The stem conv (and
# the ImageNet stem's max pool) and the classifier stay float; every block
# runs on int8 codes. ``layer_plan`` compiles the config into named steps,
# each reading its input by name, so the walk needs no model-name branch:
#
#   projection (first block of a stage, when the shape changes): 1x1,
#       stride s, signed codes on the stage's stream scale;
#   the branch: 3x3, 3x3 (basic) or 1x1, 3x3, 1x1 (bottleneck, v1.5), the
#       stride s on the block's first 3x3, quantized ReLU between convs;
#   the last conv + add: signed requant onto the stream scale, plus the
#       shortcut's codes (the block input or the projection's output),
#       clipped to [0, n] — the add and the ReLU after it, fused into the
#       conv's epilogue (``integer_inference.int_conv2d_add``).
#
# Within a stage every producer onto the residual stream — the projection,
# each block's last conv, each block input — shares one scale, so the add
# is a plain code add; ``handoff_edges`` lists these ties and
# ``convert_int`` refuses a stack that breaks them.
# ---------------------------------------------------------------------------


class Step(NamedTuple):
    """One step of the integer deployment plan."""

    op: str               # "fp_conv" | "pool" | "conv" | "conv_add"
    name: str = ""        # parameter name (convs)
    src: str = ""         # input: "entry" or the producing step's name
    ksize: int = 1        # conv kernel, or the pool's window
    stride: int = 1
    cin: int = 0
    cout: int = 0
    relu_out: bool = True  # False: signed codes (projection, pre-add)
    shortcut: str = ""    # conv_add: the codes added to the branch


def _block_steps(pre: str, src: str, cin: int, width: int, stride: int,
                 cfg: ResNetConfig):
    """Steps of one block reading ``src``; its output is the last step's."""
    cout = width * cfg.expansion
    steps, shortcut = [], src
    if stride != 1 or cin != cout:
        steps.append(Step("conv", pre + "_sc", src, 1, stride, cin, cout,
                          relu_out=False))
        shortcut = pre + "_sc"
    kernels = (3, 3) if cfg.block == "basic" else (1, 3, 1)
    strided = kernels.index(3)  # the stride sits on the first 3x3 (v1.5)
    for i, ks in enumerate(kernels):
        name = f"{pre}_c{i + 1}"
        s = stride if i == strided else 1
        if i < len(kernels) - 1:
            steps.append(Step("conv", name, src, ks, s, cin, width))
        else:
            steps.append(Step("conv_add", name, src, ks, s, cin, cout,
                              relu_out=False, shortcut=shortcut))
        src, cin = name, width
    return steps


def layer_plan(cfg: ResNetConfig):
    """cfg -> ordered steps: the float stem conv, the float pre-entry max
    pool (ImageNet stem), then every block's integer convs."""
    if cfg.block not in ("basic", "bottleneck"):
        raise ValueError(f"unknown block kind {cfg.block!r}")
    ks, stride, pool = STEMS[cfg.stem]
    plan = [Step("fp_conv", "stem", "", ks, stride, cfg.in_channels,
                 cfg.widths[0])]
    if pool is not None:
        plan.append(Step("pool", ksize=pool[0], stride=pool[1]))
    src, cin = "entry", cfg.widths[0]
    for si, (width, n_blocks) in enumerate(zip(cfg.widths,
                                               cfg.stage_blocks)):
        for bi in range(n_blocks):
            stride = 2 if si > 0 and bi == 0 else 1
            block = _block_steps(f"s{si}b{bi}", src, cin, width, stride, cfg)
            plan += block
            src, cin = block[-1].name, block[-1].cout
    return plan


def _int_steps(cfg: ResNetConfig):
    return [s for s in layer_plan(cfg) if s.op in ("conv", "conv_add")]


def int_conv_names(cfg: ResNetConfig):
    """Names of the integer convs, in plan order (the noise rng schedule;
    the first reads the entry codes, the last's codes leave the core)."""
    return [s.name for s in _int_steps(cfg)]


def handoff_edges(cfg: ResNetConfig):
    """Scale-tie edges of the residual DAG, topologically ordered.

    Every conv's ``s_in`` ties to its input's scale: the producing conv's
    ``s_out``, or for the entry codes the first entry reader's ``s_in``.
    Every last conv's ``s_out`` ties to its shortcut's scale, which puts
    each block input, each last conv and the projection of a stage on the
    stage's one stream scale. One ``sync_handoff_edges`` pass ties a
    whole stack.
    """
    steps = _int_steps(cfg)
    root = steps[0].name

    def scale_of(src):
        return (root, "s_in") if src == "entry" else (src, "s_out")

    edges = []
    for s in steps:
        if s.name != root:
            edges.append((*scale_of(s.src), s.name, "s_in"))
        if s.op == "conv_add":
            edges.append((*scale_of(s.shortcut), s.name, "s_out"))
    return edges


def standin_params(key, cfg: ResNetConfig, *, s: float = 0.5):
    """Deterministic untrained FQ (BN-free) stand-in of the integer path:
    He-normal weights, every activation scale pinned to ``s`` and the DAG
    tied — a convertible stack with a valid hand-off for analysis targets
    and tests, not a trained model."""
    from ..core import integer_inference as ii
    steps = _int_steps(cfg)
    keys = iter(jax.random.split(key, len(steps) + 2))
    params = {"stem": fql.init_fq_conv2d(next(keys), STEMS[cfg.stem][0],
                                         cfg.in_channels, cfg.widths[0])}
    for st in steps:
        params[st.name] = {**fql.init_fq_conv2d(next(keys), st.ksize, st.cin,
                                                st.cout),
                           "s_in": jnp.float32(s), "s_out": jnp.float32(s)}
    params["head"] = fql.init_dense(next(keys), steps[-1].cout,
                                    cfg.num_classes)
    return ii.sync_handoff_edges(params, handoff_edges(cfg))


def int_extras(params, state, cfg: ResNetConfig):
    """Float-side extras (stem conv, classifier, entry and decode scales)."""
    names = int_conv_names(cfg)
    return {"stem": params["stem"], "head": params["head"],
            "entry": {"s_in": params[names[0]]["s_in"]},
            "s_out_last": params[names[-1]]["s_out"]}


def convert_int(params, state, qcfg: QuantConfig, cfg: ResNetConfig,
                weight_format=None):
    """Trained FQ (BN-folded) params -> ConvertedStack over the residual
    DAG; refuses a stack whose scales break ``handoff_edges``.
    ``weight_format`` as in ``integer_inference.convert_stack``."""
    from ..core import integer_inference as ii
    if n_levels(qcfg.bits_a) != n_levels(qcfg.bits_out):
        raise ValueError(
            f"an integer residual stream needs n_levels(bits_a) == "
            f"n_levels(bits_out) so entry and block codes share a "
            f"denominator across the add (got bits_a={qcfg.bits_a}, "
            f"bits_out={qcfg.bits_out})")
    steps = _int_steps(cfg)
    return ii.convert_stack({s.name: params[s.name] for s in steps}, qcfg,
                            specs=[ii.LayerSpec(s.name, relu_out=s.relu_out)
                                   for s in steps],
                            extras=int_extras(params, state, cfg),
                            weight_format=weight_format,
                            handoff_edges=handoff_edges(cfg))


def _layer_rngs(rng, n):
    return list(jax.random.split(rng, n)) if rng is not None else [None] * n


def int_entry(ip, x, qcfg: QuantConfig, cfg: ResNetConfig):
    """The float prefix: (B, H, W, C) -> the core's entry codes (stem conv
    at the edge precision, the ImageNet stem's max pool, entry quantizer).
    Padding is k // 2 on every side (not XLA's "SAME", which pads a
    stride-2 conv unevenly), as in the published model."""
    from ..core import integer_inference as ii
    h = x
    for step in layer_plan(cfg):
        if step.op == "fp_conv":
            p = step.ksize // 2
            with fql.edge_precision():
                h = fql.fq_conv2d(ip["stem"], h, QuantConfig(fq=qcfg.fq),
                                  stride=step.stride,
                                  padding=((p, p), (p, p)),
                                  b_in=WEIGHT_BOUND)
        elif step.op == "pool":
            p = step.ksize // 2
            h = jax.lax.reduce_window(
                h, np.float32(-np.inf), jax.lax.max,
                (1, step.ksize, step.ksize, 1),
                (1, step.stride, step.stride, 1),
                ((0, 0), (p, p), (p, p), (0, 0)))
    return ii.entry_codes(h, ip["entry"], qcfg, b_in=RELU_BOUND)


def int_core(ip, codes, qcfg: QuantConfig, cfg: ResNetConfig, *, impl=None,
             noise: Optional[NoiseConfig] = None, rng=None,
             mac_chunks: int = 1):
    """The integer segment alone: entry codes -> the last block's codes.

    Walks the plan's integer steps, each reading its input by name. Single
    source of truth: ``int_apply`` calls it and ``repro.analysis`` traces
    it. One rng per integer conv, in plan order.
    """
    from ..core import integer_inference as ii
    steps = _int_steps(cfg)
    rngs = _layer_rngs(rng, len(steps))
    vals = {"entry": codes}
    for step, r in zip(steps, rngs):
        kw = dict(ksize=step.ksize, stride=step.stride,
                  padding=step.ksize // 2, impl=impl, noise=noise, rng=r,
                  mac_chunks=mac_chunks)
        if step.op == "conv":
            vals[step.name] = ii.int_conv2d(ip[step.name], vals[step.src],
                                            **kw)
        else:
            vals[step.name] = ii.int_conv2d_add(
                ip[step.name], vals[step.src], vals[step.shortcut], **kw)
    return vals[steps[-1].name]


def int_exit(ip, codes, qcfg: QuantConfig):
    """The float suffix: last block codes -> logits (decode, global
    average pool, classifier at the edge precision)."""
    from ..core import integer_inference as ii
    h = jnp.mean(ii.decode_output(codes, ip["s_out_last"], qcfg.bits_out),
                 axis=(1, 2))
    with fql.edge_precision():
        return fql.dense(ip["head"], h)


def int_apply(ip, x, qcfg: QuantConfig, cfg: ResNetConfig, *, impl=None,
              noise: Optional[NoiseConfig] = None, rng=None,
              mac_chunks: int = 1):
    """x: (B, H, W, C) -> logits; codes flow from the entry quantizer
    through every block. ``noise`` + ``rng`` run the §4.4 noise model on
    every integer conv; the float stem and classifier stay clean."""
    codes = int_entry(ip, x, qcfg, cfg)
    codes = int_core(ip, codes, qcfg, cfg, impl=impl, noise=noise, rng=rng,
                     mac_chunks=mac_chunks)
    return int_exit(ip, codes, qcfg)


def int_serve_fn(ip, qcfg: QuantConfig, cfg: ResNetConfig, **kw):
    """Fixed-signature closure for serve.cnn_batching: (B, H, W, C) ->
    logits; ``noise``/``rng`` pass through to ``int_apply``."""
    def fn(x, noise=None, rng=None):
        return int_apply(ip, x, qcfg, cfg, noise=noise, rng=rng, **kw)
    return fn
