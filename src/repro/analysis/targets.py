"""Analysis targets: the stacks + traces + shapes the passes run over.

Self-contained stand-in stacks (init-and-fold with a consistent FQ
hand-off — mirrors the benchmarks' stand-in recipe without importing
from ``benchmarks/``), the declared conv geometries each stack serves
(for kernellint), and :func:`run_analysis`, the one-call driver the CLI
and the tests share.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import quant
from ..core.noise import NoiseConfig
from ..core.quant import QuantConfig, n_levels
from ..models import darknet, fq_lm, kws, resnet
from . import intlint, kernellint, planlint
from .intlint import TraceSpec
from .kernellint import ConvShape
from .report import Report, Suppression

DEFAULT_QCFG = QuantConfig(2, 4, 4, fq=True)
DEFAULT_MAC_CHUNKS = (1, 4, 16)
# Table 7's harshest condition — worst case for interval blow-up.
DEFAULT_NOISE = NoiseConfig(0.30, 0.30, 1.50)
# Declared serving input extents (the shape-ladder rungs the batcher
# folds onto): KWS serves cfg.seq_len MFCC frames; darknet serves the
# paper's ImageNet letterbox (reduced stacks serve the benchmark size).
DARKNET_INPUT = 224
DARKNET_REDUCED_INPUT = 28
RESNET_INPUT = 224
RESNET_REDUCED_INPUT = 32

_STANDIN_CACHE: Dict = {}

# Repo-wide reasoned exemptions (docs/ANALYSIS.md "Suppressions"). Every
# entry must say WHY the finding is acceptable — an empty tuple means the
# checked-in tree is finding-free at the default gate.
DEFAULT_SUPPRESSIONS: Tuple = ()


@dataclasses.dataclass
class StackTarget:
    """Everything the three passes need to know about one stack."""

    name: str
    module: object
    cfg: object
    qcfg: QuantConfig
    fq_params: dict
    stack: object                  # ConvertedStack
    chain: List[str]               # code-carrying layer names, in order
    shapes: List[ConvShape]        # served conv geometries
    plan: Optional[list] = None    # darknet-style plan (fused-pool lint)
    n_pool_markers: int = 0
    core_example: Tuple = ()       # example codes for int_core tracing
    weight_format: str = "int8"    # packed storage the stack was built with
    # residual-add DAG stacks declare scale-tie edges instead of the
    # pairwise chain contract, and may pin their own impl list (matmul
    # cores have a single integer impl)
    handoff_edges: Optional[list] = None
    impls: Optional[Tuple[str, ...]] = None


def _resolve_format(qcfg: QuantConfig, weight_format: Optional[str]) -> str:
    if weight_format is None:
        return "int8"
    if weight_format == "auto":
        return quant.auto_weight_format(n_levels(qcfg.bits_w))
    return weight_format


def _standin(module, cfg, names, qcfg, *, s_out=0.2, seed=0,
             weight_format="int8"):
    """Init-and-fold integer stand-in with a consistent hand-off chain
    (same recipe as the benchmarks' ``trained_int_params``)."""
    key = (module.__name__, cfg, tuple(names), qcfg, float(s_out), int(seed),
           weight_format)
    hit = _STANDIN_CACHE.get(key)
    if hit is not None:
        return hit
    params, state = module.init(jax.random.key(seed), cfg)
    params = module.to_fq(params, state, cfg)
    for n in names:
        params[n]["s_out"] = jnp.float32(s_out)
    for a, b in zip(names, names[1:]):
        params[b]["s_in"] = params[a]["s_out"]
    out = (params, state, module.convert_int(params, state, qcfg, cfg,
                                             weight_format=weight_format))
    _STANDIN_CACHE[key] = out
    return out


# ---------------------------------------------------------------------------
# declared conv geometries
# ---------------------------------------------------------------------------


def kws_conv_shapes(cfg, batch: int = 1,
                    weight_format: str = "int8") -> List[ConvShape]:
    shapes = []
    t, cin = cfg.seq_len, cfg.embed
    for name, dil in kws.layer_plan(cfg):
        t_out = t - dil * (cfg.ksize - 1)
        shapes.append(ConvShape(
            name=f"kws/{name}", ho=t_out, wo=1, cin=cin, cout=cfg.filters,
            kh=cfg.ksize, kw=1, dilation=(dil, 1),
            weight_format=weight_format))
        t, cin = t_out, cfg.filters
    return shapes


def darknet_conv_shapes(cfg, input_hw: int, batch: int = 1,
                        weight_format: str = "int8") -> List[ConvShape]:
    """Geometries of the INTEGER convs (the FP edge convs never hit the
    int kernels). SAME padding keeps H through convs; pools floor-halve."""
    convs = [l for l in cfg.layers if l != "M"]
    couts = {f"conv{i}": co for i, (_, co) in enumerate(convs)}
    cins = {}
    cin = cfg.in_channels
    for i, (_, co) in enumerate(convs):
        cins[f"conv{i}"] = cin
        cin = co
    shapes = []
    h = input_hw
    plan = darknet.layer_plan(cfg)
    for step in plan:
        if step[0] == "fp_conv":
            continue                      # FP edge conv, SAME: h unchanged
        if step[0] == "pool":
            h = h // 2
            continue
        _, name, ks, pooled = step
        shapes.append(ConvShape(
            name=f"darknet/{name}", ho=h, wo=h, cin=cins[name],
            cout=couts[name], kh=ks, kw=ks,
            pool=(2, 2) if pooled else None, weight_format=weight_format))
        if pooled:
            h = h // 2
    return shapes


# ---------------------------------------------------------------------------
# stack targets
# ---------------------------------------------------------------------------


def kws_target(qcfg: QuantConfig = DEFAULT_QCFG, *, reduced: bool = False,
               batch: int = 1,
               weight_format: Optional[str] = None) -> StackTarget:
    fmt = _resolve_format(qcfg, weight_format)
    cfg = kws.KWSConfig.reduced() if reduced else kws.KWSConfig()
    names = kws.conv_names(cfg)
    fq_params, _, stack = _standin(kws, cfg, names, qcfg, weight_format=fmt)
    codes = jnp.zeros((batch, cfg.seq_len, cfg.embed), jnp.int8)
    name = "kws-reduced" if reduced else "kws"
    if fmt != "int8":
        name = f"{name}-{fmt}"
    return StackTarget(
        name=name,
        module=kws, cfg=cfg, qcfg=qcfg, fq_params=fq_params, stack=stack,
        chain=names, shapes=kws_conv_shapes(cfg, batch, weight_format=fmt),
        core_example=(codes,), weight_format=fmt)


def darknet_target(qcfg: QuantConfig = DEFAULT_QCFG, *,
                   reduced: bool = False, batch: int = 1,
                   weight_format: Optional[str] = None) -> StackTarget:
    fmt = _resolve_format(qcfg, weight_format)
    cfg = darknet.DarkNetConfig.reduced() if reduced else darknet.DarkNetConfig()
    input_hw = DARKNET_REDUCED_INPUT if reduced else DARKNET_INPUT
    all_names = [f"conv{i}" for i in
                 range(len([l for l in cfg.layers if l != "M"]))]
    fq_params, _, stack = _standin(darknet, cfg, all_names, qcfg,
                                   weight_format=fmt)
    plan = darknet.layer_plan(cfg)
    # core input: codes right after the FP prefix (conv0 + pre-entry pools)
    h = input_hw
    for step in plan[:darknet._split_plan(plan)]:
        if step[0] == "pool":
            h = h // 2
    convs = [l for l in cfg.layers if l != "M"]
    codes = jnp.zeros((batch, h, h, convs[0][1]), jnp.int8)
    name = "darknet-reduced" if reduced else "darknet"
    if fmt != "int8":
        name = f"{name}-{fmt}"
    return StackTarget(
        name=name,
        module=darknet, cfg=cfg, qcfg=qcfg, fq_params=fq_params,
        stack=stack, chain=darknet.int_conv_names(cfg),
        shapes=darknet_conv_shapes(cfg, input_hw, batch, weight_format=fmt),
        plan=plan, n_pool_markers=sum(1 for l in cfg.layers if l == "M"),
        core_example=(codes,), weight_format=fmt)


def resnet_conv_shapes(cfg, input_hw: int,
                       weight_format: str = "int8") -> List[ConvShape]:
    """Geometries of the integer convs of a residual net: every conv and
    pool pads k // 2, so a stride-s step takes a side h to ceil(h / s)."""
    sides = {}
    shapes = []
    for step in resnet.layer_plan(cfg):
        if step.op in ("fp_conv", "pool"):
            input_hw = -(-input_hw // step.stride)
            sides["entry"] = input_hw
            continue
        h = -(-sides[step.src] // step.stride)
        sides[step.name] = h
        shapes.append(ConvShape(
            name=f"resnet/{step.name}", ho=h, wo=h, cin=step.cin,
            cout=step.cout, kh=step.ksize, kw=step.ksize,
            stride=(step.stride, step.stride), weight_format=weight_format))
    return shapes


def resnet_target(qcfg: QuantConfig = DEFAULT_QCFG, *,
                  reduced: bool = False, batch: int = 1) -> StackTarget:
    """The integer residual core over its DAG: plain convs and fused
    conv + add steps, stride 1 and 2, identity and projection shortcuts;
    planlint re-proves the stream's scale ties (``handoff_edges``)."""
    cfg = (resnet.ResNetConfig.reduced_bottleneck() if reduced
           else resnet.ResNetConfig.resnet50())
    input_hw = RESNET_REDUCED_INPUT if reduced else RESNET_INPUT
    key = ("resnet", cfg, qcfg)
    hit = _STANDIN_CACHE.get(key)
    if hit is None:
        params = resnet.standin_params(jax.random.key(0), cfg)
        hit = (params, resnet.convert_int(params, {}, qcfg, cfg))
        _STANDIN_CACHE[key] = hit
    fq_params, stack = hit
    shapes = resnet_conv_shapes(cfg, input_hw)
    h = shapes[0].ho  # the first stage keeps the entry plane
    codes = jnp.zeros((batch, h, h, cfg.widths[0]), jnp.int8)
    return StackTarget(
        name="resnet-reduced" if reduced else "resnet",
        module=resnet, cfg=cfg, qcfg=qcfg, fq_params=fq_params, stack=stack,
        chain=resnet.int_conv_names(cfg), shapes=shapes,
        core_example=(codes,), handoff_edges=resnet.handoff_edges(cfg))


def lm_target(qcfg: QuantConfig = DEFAULT_QCFG, *, reduced: bool = False,
              batch: int = 1, seq: int = 4) -> StackTarget:
    """The integer transformer core over its residual-add DAG.

    The core's example args are the two integer-segment entries: stream
    codes plus per-layer stand-in attention-island output codes (the
    float softmax island itself is outside the traced integer core —
    see ``fq_lm.int_core``). Matmuls have one integer impl, so the
    target pins ``impls=("int8",)``.
    """
    cfg = fq_lm.FQLMConfig.reduced() if reduced else fq_lm.FQLMConfig()
    key = ("fq_lm", cfg, qcfg)
    hit = _STANDIN_CACHE.get(key)
    if hit is None:
        params = fq_lm.standin_params(jax.random.key(0), cfg)
        hit = (params, fq_lm.convert_int(params, cfg, qcfg))
        _STANDIN_CACHE[key] = hit
    fq_params, stack = hit
    codes = jnp.zeros((batch, seq, cfg.d_model), jnp.int8)
    attn = jnp.zeros((cfg.n_layers, batch, seq, cfg.d_model), jnp.int8)
    return StackTarget(
        name="lm-reduced" if reduced else "lm",
        module=fq_lm, cfg=cfg, qcfg=qcfg, fq_params=fq_params, stack=stack,
        chain=fq_lm.proj_names(cfg), shapes=[],
        core_example=(codes, attn),
        handoff_edges=fq_lm.handoff_edges(cfg), impls=("int8",))


def default_targets(qcfg: QuantConfig = DEFAULT_QCFG, *,
                    reduced: bool = False) -> List[StackTarget]:
    # int8 stacks plus their packed (auto: ternary at the default
    # 2-bit-weight qcfg) twins — the packed cores are traced and their
    # served shape keys linted exactly like the int8 ones — plus the
    # integer transformer core over its residual-add DAG.
    return [kws_target(qcfg, reduced=reduced),
            darknet_target(qcfg, reduced=reduced),
            kws_target(qcfg, reduced=reduced, weight_format="auto"),
            darknet_target(qcfg, reduced=reduced, weight_format="auto"),
            lm_target(qcfg, reduced=reduced)]


# ---------------------------------------------------------------------------
# trace specs
# ---------------------------------------------------------------------------


def core_traces(target: StackTarget, *, impls: Sequence[str] = ("im2col",
                "fused"), mac_chunks: Sequence[int] = DEFAULT_MAC_CHUNKS,
                noise: NoiseConfig = DEFAULT_NOISE) -> List[TraceSpec]:
    """Clean + noisy int_core traces for one stack: every impl, and the
    noise model at every requested mac_chunks. A target that pins its own
    ``impls`` (the matmul LM core) overrides the requested impl list."""
    ip, qcfg, cfg, mod = (target.stack, target.qcfg, target.cfg,
                          target.module)
    rng = jax.random.key(7)
    # packed cores additionally prove the unpacked weight operand of every
    # contraction decodes into the declared format's sign-extended range
    wr = (quant.format_interval(target.weight_format)
          if target.weight_format != "int8" else None)
    specs = []
    for impl in (target.impls or impls):
        def clean(*ex, impl=impl):
            return mod.int_core(ip, *ex, qcfg, cfg, impl=impl)

        specs.append(TraceSpec(f"{target.name}/{impl}/clean", clean,
                               target.core_example, weight_range=wr))
        for k in mac_chunks:
            def noisy(*ex, impl=impl, k=k):
                return mod.int_core(ip, *ex, qcfg, cfg, impl=impl,
                                    noise=noise, rng=rng, mac_chunks=k)

            specs.append(TraceSpec(
                f"{target.name}/{impl}/noise/mac_chunks={k}", noisy,
                target.core_example, weight_range=wr))
    return specs


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


def run_analysis(targets: Sequence[StackTarget], *,
                 mac_chunks: Sequence[int] = DEFAULT_MAC_CHUNKS,
                 impls: Sequence[str] = ("im2col", "fused"),
                 suppressions: Optional[Sequence[Suppression]] = None,
                 table_path: Optional[str] = None,
                 skip_intlint: bool = False) -> Report:
    """All three passes over the given stacks; one merged Report.

    ``table_path`` lints a candidate autotune table file instead of the
    checked-in one (schema + the block picks it would produce).
    """
    if suppressions is None:
        suppressions = DEFAULT_SUPPRESSIONS
    report = Report(suppressions)
    shape_kw = {}
    if table_path is not None:
        from ..kernels import fq_conv
        kernellint.lint_table_schema(report, table_path)
        # load_autotune_table overlays builtins with the candidate file
        shape_kw = {"table": fq_conv.load_autotune_table(table_path),
                    "measured": fq_conv.measured_keys(table_path)}
    else:
        kernellint.lint_table_schema(report)
    for t in targets:
        if t.handoff_edges is not None:
            planlint.lint_handoff_edges(t.fq_params, t.handoff_edges,
                                        report, t.name)
        else:
            planlint.lint_handoff(t.fq_params, t.chain, report, t.name)
        planlint.lint_stack(t.stack, report, t.name,
                            layer_params=t.fq_params)
        planlint.lint_noise_seeds(t.chain, report, t.name)
        if t.plan is not None:
            planlint.lint_fused_pools(t.plan, t.n_pool_markers, report,
                                      t.name, stack=t.stack)
        kernellint.lint_shapes(t.shapes, report, **shape_kw)
        if not skip_intlint:
            for spec in core_traces(t, impls=impls, mac_chunks=mac_chunks):
                intlint.lint_trace(spec, report)
    kernellint.runtime_miss_counters(report)
    return report
