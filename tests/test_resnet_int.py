"""Integer ResNets over the residual DAG: the fused conv + add path against
its im2col oracle and against the benchmark's plain reference, for basic
and bottleneck blocks, stride 1 and 2, identity and projection shortcuts;
the stream's scale ties; noisy replay; the stream's code spread.

Weights are the benchmark's seeded, calibrated checkpoint at a reduced
size (``bench/models/resnet.py``), so the reference comparison is the one
that decides the benchmark's ``correct``."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import cells, refops  # noqa: E402
from bench.tests import helpers  # noqa: E402
from repro.core import integer_inference as ii  # noqa: E402
from repro.core.noise import NoiseConfig  # noqa: E402
from repro.models import resnet  # noqa: E402

M = cells.model("resnet")
SMALL = {
    "bottleneck": dict(widths=[8, 16], blocks_per_stage=[2, 2],
                       input_hw=[32, 32], num_classes=10),
    "basic": dict(widths=[8, 16], blocks_per_stage=[2, 2], block="basic",
                  expansion=1, stem="cifar", input_hw=[16, 16],
                  num_classes=10),
}


@pytest.fixture(scope="module", params=sorted(SMALL))
def net(request):
    spec = helpers.spec("resnet50", **SMALL[request.param])
    params, state = M.checkpoint(spec)
    cfg, qcfg = M.model_config(spec), M.quant_config(spec)
    ip = resnet.convert_int(params, state, qcfg, cfg)
    x = np.random.default_rng(3).standard_normal(
        (4,) + M.rung_shape(spec)).astype(np.float32)
    return dict(kind=request.param, spec=spec, params=params, cfg=cfg,
                qcfg=qcfg, ip=ip, x=x)


def _walk(ip, codes, cfg, impl):
    """Every integer step's output codes, by name (``int_core``'s walk)."""
    vals = {"entry": codes}
    for s in resnet.layer_plan(cfg):
        if s.op == "conv":
            vals[s.name] = ii.int_conv2d(ip[s.name], vals[s.src],
                                         ksize=s.ksize, stride=s.stride,
                                         padding=s.ksize // 2, impl=impl)
        elif s.op == "conv_add":
            vals[s.name] = ii.int_conv2d_add(
                ip[s.name], vals[s.src], vals[s.shortcut], ksize=s.ksize,
                stride=s.stride, padding=s.ksize // 2, impl=impl)
    return vals


def test_plan_covers_strides_and_shortcuts(net):
    plan = resnet.layer_plan(net["cfg"])
    convs = [s for s in plan if s.op in ("conv", "conv_add")]
    assert {s.stride for s in convs} == {1, 2}
    adds = [s for s in convs if s.op == "conv_add"]
    projections = [s for s in convs if s.name.endswith("_sc")]
    assert projections and all(not s.relu_out for s in projections)
    identity = [s for s in adds if not s.shortcut.endswith("_sc")]
    assert identity and len(identity) + len(projections) == len(adds)
    assert [s.ksize for s in convs if s.name.startswith("s1b0_c")] == (
        [1, 3, 1] if net["kind"] == "bottleneck" else [3, 3])


def test_fused_add_bit_identical_to_im2col(net):
    entry = resnet.int_entry(net["ip"], jnp.asarray(net["x"]), net["qcfg"],
                             net["cfg"])
    fused = _walk(net["ip"], entry, net["cfg"], "fused")
    oracle = _walk(net["ip"], entry, net["cfg"], "im2col")
    assert fused.keys() == oracle.keys()
    for name in fused:
        np.testing.assert_array_equal(np.asarray(fused[name]),
                                      np.asarray(oracle[name]), err_msg=name)
    out = {impl: np.asarray(resnet.int_apply(
        net["ip"], jnp.asarray(net["x"]), net["qcfg"], net["cfg"],
        impl=impl)) for impl in ("fused", "im2col")}
    np.testing.assert_array_equal(out["fused"], out["im2col"])


@pytest.mark.parametrize("impl", ["fused", "im2col"])
def test_matches_plain_reference(net, impl):
    """Both paths against the benchmark's plain reference, under the
    benchmark's own limit: the integer core is exact on both sides, so the
    gap is float rounding in the stem and the classifier."""
    ref = M.Reference(net["spec"], net["params"], {}).logits(list(net["x"]))
    out = np.asarray(resnet.int_serve_fn(net["ip"], net["qcfg"], net["cfg"],
                                         impl=impl)(jnp.asarray(net["x"])))
    assert refops.logit_gap(out, ref).max() <= \
        net["spec"]["limits"]["logit_gap"]


def test_broken_stream_scale_is_refused(net):
    adds = [s for s in resnet.layer_plan(net["cfg"]) if s.op == "conv_add"]
    name = adds[-1].name
    bad = dict(net["params"])
    bad[name] = {**bad[name], "s_out": bad[name]["s_out"] + 0.1}
    with pytest.raises(ValueError, match="hand-off contract"):
        resnet.convert_int(bad, {}, net["qcfg"], net["cfg"])
    tied = ii.sync_handoff_edges(bad, resnet.handoff_edges(net["cfg"]))
    assert float(tied[name]["s_out"]) != float(bad[name]["s_out"])
    resnet.convert_int(tied, {}, net["qcfg"], net["cfg"])


def test_noisy_replay_bit_exact_across_impls(net):
    noise = NoiseConfig(0.3, 0.3, 1.5)
    rng = jax.random.key(11)
    x = jnp.asarray(net["x"])
    runs = [np.asarray(resnet.int_apply(net["ip"], x, net["qcfg"],
                                        net["cfg"], impl=impl, noise=noise,
                                        rng=rng))
            for impl in ("fused", "im2col", "fused")]
    np.testing.assert_array_equal(runs[0], runs[1])
    np.testing.assert_array_equal(runs[0], runs[2])
    clean = np.asarray(resnet.int_apply(net["ip"], x, net["qcfg"],
                                        net["cfg"], impl="im2col"))
    assert not np.array_equal(runs[0], clean)


def test_stream_codes_spread_at_every_block_output(net):
    n = refops.n_levels(net["spec"]["quant"]["bits_out"])
    entry = resnet.int_entry(net["ip"], jnp.asarray(net["x"]), net["qcfg"],
                             net["cfg"])
    vals = _walk(net["ip"], entry, net["cfg"], "im2col")
    for s in resnet.layer_plan(net["cfg"]):
        if s.op == "conv_add":
            codes = np.asarray(vals[s.name])
            assert len(np.unique(codes)) == n + 1, s.name
            assert (codes == n).mean() <= 0.05, s.name


def test_resnet50_plan_shape():
    cfg = resnet.ResNetConfig.resnet50()
    convs = [s for s in resnet.layer_plan(cfg)
             if s.op in ("conv", "conv_add")]
    assert len(convs) == 52
    assert sum(s.op == "conv_add" for s in convs) == 16
    assert sum(s.name.endswith("_sc") for s in convs) == 4
    assert [s.name for s in convs if s.stride == 2] == [
        f"s{i}b0_{c}" for i in (1, 2, 3) for c in ("sc", "c2")]
    assert convs[-1].cout == 2048


def test_analysis_proves_the_residual_core():
    """intlint: the reduced residual core is integer-pure with int32
    headroom on both impls, clean and noisy; planlint: its DAG hand-off
    edges hold."""
    from repro.analysis import intlint, planlint, targets
    from repro.analysis.report import Report
    t = targets.resnet_target(reduced=True)
    r = Report()
    planlint.lint_handoff_edges(t.fq_params, t.handoff_edges, r, t.name)
    planlint.lint_stack(t.stack, r, t.name, layer_params=t.fq_params)
    for spec in targets.core_traces(t, mac_chunks=(1,)):
        intlint.lint_trace(spec, r)
    assert r.findings == [], [f.message for f in r.findings]
    assert {"planlint/handoff", "intlint"} <= {p["check"] for p in r.proofs}
    assert len([p for p in r.proofs if p["check"] == "intlint"]) == 4
