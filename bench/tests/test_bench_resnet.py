"""The ``resnet`` configuration and its cells: cost functions against hand
counts, the control of the output check at full size, a tiny window
through the real session, the fused add's roofline reader, and the
four-lane cell's mix."""
import types

import jax
import numpy as np
import pytest

from bench import cells, session, traffic_gen
from bench.tests import helpers

# ResNet-50 v1.5 at 224x224 (He et al. 2016, Table 1): per stage the
# output side, width and block count; the stem and max pool leave 56x56x64
STAGES = [(56, 64, 3), (28, 128, 4), (14, 256, 6), (7, 512, 3)]
TINY = dict(widths=[8, 16], blocks_per_stage=[2, 2], input_hw=[32, 32],
            num_classes=10,
            batcher={"rungs": [32], "max_batch": 4, "dispatch_ahead": True,
                     "max_inflight": 2, "max_wait_ticks": 0})


def _hand_convs():
    """(input side, output side, kernel, cin, cout, is_last) of the 52
    integer convs, written from the published table."""
    convs, cin, h_in = [], 64, 56
    for si, (h, width, blocks) in enumerate(STAGES):
        for bi in range(blocks):
            cout = 4 * width
            if bi == 0:
                convs.append((h_in, h, 1, cin, cout, False))   # projection
            convs += [(h_in, h_in, 1, cin, width, False),       # conv1
                      (h_in, h, 3, width, width, False),        # conv2 (v1.5)
                      (h, h, 1, width, cout, True)]             # conv3 + add
            cin, h_in = cout, h
    return convs


def test_resnet50_ops_per_request():
    spec = helpers.spec("resnet50")
    ops = cells.model("resnet").request_ops(spec)
    hand = _hand_convs()
    assert len(hand) == 52
    core = sum(2 * ho * ho * k * k * ci * co for _, ho, k, ci, co, _ in hand)
    edges = 2 * 112 * 112 * 7 * 7 * 3 * 64 + 2 * 2048 * 1000
    assert ops == {"int": core, "float": edges}
    assert ops["int"] == pytest.approx(7.938e9, rel=1e-3)
    assert ops["float"] == pytest.approx(0.240e9, rel=1e-3)


@pytest.mark.parametrize("batch", [1, 8])
def test_kernel_calls_cover_every_integer_conv_once(batch):
    spec = helpers.spec("resnet50")
    model = cells.model("resnet")
    plain = model.fq_conv_calls(spec, batch)
    adds = model.fq_conv_add_calls(spec, batch)
    assert (len(plain), len(adds)) == (36, 16)
    assert sum(o for o, _ in plain + adds) == \
        batch * model.request_ops(spec)["int"]
    hand = _hand_convs()
    want_plain = [(2 * batch * ho * ho * k * k * ci * co,
                   batch * hi * hi * ci + k * k * ci * co
                   + batch * ho * ho * co)
                  for hi, ho, k, ci, co, last in hand if not last]
    want_add = [(2 * batch * ho * ho * k * k * ci * co,
                 batch * hi * hi * ci + k * k * ci * co
                 + 2 * batch * ho * ho * co)  # shortcut read, output written
                for hi, ho, k, ci, co, last in hand if last]
    assert sorted(plain) == sorted(want_plain)
    assert sorted(adds) == sorted(want_add)


def test_control_fails_the_limit_at_full_size():
    """The plain reference with bf16x3 float edges (the TPU's ``high``)
    in the program's place fails ``logit_gap`` on 8 of the mix's
    payloads; the reference itself reads 0."""
    spec = helpers.spec("resnet50")
    model = cells.model("resnet")
    params, state = model.checkpoint(spec)
    pool = traffic_gen.payload_pool(
        cells.traffic("closed32_img"),
        lambda dims: model.payload_shape(spec, dims), 2 ** 31 + 9)[:8]
    ref = model.Reference(spec, params, state).logits(pool)
    ctl = model.Reference(spec, params, state, "bf16x3").logits(pool)
    limit = spec["limits"]["logit_gap"]
    assert session.gap_of(list(enumerate(ref)), ref) == 0.0
    assert session.gap_of(list(enumerate(ctl)), ref) > 3 * limit


@pytest.mark.parametrize("alter", [False, True])
def test_tiny_window_through_the_session(alter):
    spec = helpers.spec("resnet50", **TINY)
    spec["limits"]["logit_gap"] = 1e-4
    model = cells.model("resnet")
    if alter:
        program = model.program

        def broken(spec, params, state, devs):
            ladder, fns = program(spec, params, state, devs)
            return ladder, [lambda x, f=f: f(x).at[0, 0].add(0.05)
                            for f in fns]

        model = cells.model("resnet")  # a fresh copy
        model.program = broken
    cell = cells.Cell(name="tiny-resnet", chips=1, spec=spec,
                      traffic=dict(helpers.TINY_CLOSED), model=model,
                      end_to_end=[], per_layer=[])
    sess = session.Session(cell, jax.devices()[:1], lambda name: None)
    win = sess.measure(2 ** 31 + 5, 0.5, lambda name: None)
    assert win.attempted > 0 and win.failed == 0 and win.compiles == 0
    correct, checks = session.check(cell, sess.params, sess.state, win)
    if alter:
        assert not correct and checks["logit_gap"]["value"] > 1e-3
    else:
        assert correct and checks["logit_gap"]["value"] < 1e-5


def _run(op_s, slots=(8, 8, 4)):
    return types.SimpleNamespace(
        cell=cells.find_cell("resnet50-saturate"), peaks=cells.peaks(
            "TPU v5 lite"), trace={"op_s": op_s},
        window=types.SimpleNamespace(flush_slots=list(slots)))


def test_add_roofline_reads_only_fq_conv2d_add_events():
    read = cells.metric_reader("fq_conv2d_add_roofline.resnet50")
    assert read(_run({"fq_conv2d": 1.0, "fusion": 2.0})) is None
    assert read(types.SimpleNamespace(trace=None)) is None
    # a parent's program: the plain reader still reads, the new one not
    darknet = _run({"fq_conv2d": 1.0})
    darknet.cell = cells.find_cell("darknet19-saturate")
    assert read(darknet) is None
    spec, model = helpers.spec("resnet50"), cells.model("resnet")
    p = cells.peaks("TPU v5 lite")
    least = sum(max(o / p["int8_ops_per_s"], b / p["hbm_bytes_per_s"])
                for s in (8, 8, 4)
                for o, b in model.fq_conv_add_calls(spec, s))
    assert read(_run({"fq_conv2d_add": 2 * least})) == pytest.approx(50.0)


def test_four_lane_mix_keeps_four_windows_full():
    """128 clients: four lanes x 8 rows x (a window of 2 + 1 being packed)
    = 96 rows, plus a margin, over the one-lane mix's payloads."""
    one, four = cells.traffic("closed32_img"), cells.traffic("closed128_img")
    assert four["clients"] >= 4 * 8 * (2 + 1) and four["clients"] == 128
    assert {k: v for k, v in four.items()
            if k not in ("clients", "ramp_requests")} == \
        {k: v for k, v in one.items() if k not in ("clients",
                                                    "ramp_requests")}
    cell = cells.find_cell("darknet19-mesh4-saturate")
    assert cell.chips == 4 and cell.spec["batcher"]["max_batch"] == 8
    pool = traffic_gen.payload_pool(four, lambda d: tuple(d) + (3,), 7)
    assert len(pool) == 64 and all(x.dtype == np.float32 for x in pool)
