"""Cost functions against counts made by hand from the published layers."""
import pytest

from bench import cells
from bench.tests import helpers

# (output plane side, kernel, cin, cout) of DarkNet-19's 17 integer convs
# at 224x224: conv0 and the first pool leave 112x112x32.
DARKNET_INT = [
    (112, 3, 32, 64),
    (56, 3, 64, 128), (56, 1, 128, 64), (56, 3, 64, 128),
    (28, 3, 128, 256), (28, 1, 256, 128), (28, 3, 128, 256),
    (14, 3, 256, 512), (14, 1, 512, 256), (14, 3, 256, 512),
    (14, 1, 512, 256), (14, 3, 256, 512),
    (7, 3, 512, 1024), (7, 1, 1024, 512), (7, 3, 512, 1024),
    (7, 1, 1024, 512), (7, 3, 512, 1024),
]


def test_darknet19_ops_per_request():
    spec = helpers.spec("darknet19")
    ops = cells.model("darknet").request_ops(spec)
    core = sum(2 * s * s * k * k * ci * co for s, k, ci, co in DARKNET_INT)
    edges = 2 * 224 * 224 * 9 * 3 * 32 + 2 * 7 * 7 * 1024 * 1000
    assert ops == {"int": core, "float": edges}
    assert ops["int"] == pytest.approx(5.395e9, rel=1e-3)
    assert ops["float"] == pytest.approx(0.187e9, rel=1e-3)


def test_kws_ops_per_request():
    spec = helpers.spec("kws")
    ops = cells.model("kws").request_ops(spec)
    # 140 frames; VALID dilated k=3 convs leave 138, 136, 132, 124, 108,
    # 76 and 12 frames
    frames = [138, 136, 132, 124, 108, 76, 12]
    core = 2 * 45 * 3 * (100 * frames[0] + 45 * sum(frames[1:]))
    assert ops["int"] == core
    assert ops["float"] == 2 * 140 * 39 * 100 + 2 * 45 * 12
    assert ops["int"] + ops["float"] == pytest.approx(12e6, rel=0.01)


@pytest.mark.parametrize("config", ["darknet19", "kws"])
@pytest.mark.parametrize("batch", [1, 8])
def test_kernel_calls_cover_the_integer_core(config, batch):
    spec = helpers.spec(config)
    model = cells.model(spec["model"])
    calls = model.fq_conv_calls(spec, batch)
    assert sum(o for o, _ in calls) == batch * model.request_ops(spec)["int"]
    assert all(b > 0 for _, b in calls)


def test_darknet19_kernel_bytes_count_pooled_outputs_and_weights():
    spec = helpers.spec("darknet19")
    first = cells.model("darknet").fq_conv_calls(spec, 2)[0]
    # conv1 reads 2x112x112x32 codes and 3x3x32x64 int8 weights and writes
    # the pooled 2x56x56x64 plane
    assert first[1] == 2 * 112 * 112 * 32 + 9 * 32 * 64 + 2 * 56 * 56 * 64
    packed = dict(spec, weight_format="ternary")
    assert cells.model("darknet").fq_conv_calls(packed, 2)[0][1] == \
        first[1] - 9 * 32 * 64 * 0.75
