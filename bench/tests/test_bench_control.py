"""The control of the output check, at the cells' own sizes: the plain
reference with its float edges at bf16x3 (the TPU's ``high``, one step
below the "highest" the configurations state), in the program's place,
must fail the configuration's ``logit_gap`` limit. DarkNet-19 on 8 of its
mix's payloads (the CPU's share of a test run), KWS on its whole pool."""
import pytest

from bench import cells, session, traffic_gen
from bench.tests import helpers


@pytest.mark.parametrize("config,mix,n", [("darknet19", "closed32_img", 8),
                                          ("kws", "bursty_clip", None)])
def test_control_fails_the_limit(config, mix, n):
    spec = helpers.spec(config)
    model = cells.model(spec["model"])
    tr = cells.traffic(mix)
    params, state = model.checkpoint(spec)
    pool = traffic_gen.payload_pool(
        tr, lambda dims: model.payload_shape(spec, dims), 2 ** 31 + 9)[:n]
    ref = model.Reference(spec, params, state).logits(pool)
    ctl = model.Reference(spec, params, state, "bf16x3").logits(pool)
    limit = spec["limits"]["logit_gap"]
    assert session.gap_of(list(enumerate(ref)), ref) == 0.0
    assert session.gap_of(list(enumerate(ctl)), ref) > 3 * limit
