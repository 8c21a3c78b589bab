"""The traffic generator: the same seed gives the same inputs, and every
seed offers the same work in another order."""
import numpy as np
import pytest

from bench import cells, traffic_gen

SEEDS = [0, 7, 2 ** 31 + 11, 3 ** 30]


def _shape(channels):
    return lambda dims: tuple(dims) + (channels,)


@pytest.mark.parametrize("mix", ["closed32_img", "bursty_clip"])
def test_payload_pool_is_deterministic_per_seed(mix):
    tr = cells.traffic(mix)
    a = traffic_gen.payload_pool(tr, _shape(3), SEEDS[2])
    b = traffic_gen.payload_pool(tr, _shape(3), SEEDS[2])
    c = traffic_gen.payload_pool(tr, _shape(3), SEEDS[3])
    assert len(a) == tr["pool"]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(x.shape == y.shape and np.array_equal(x, y)
                   for x, y in zip(a, c))
    assert all(x.dtype == np.float32 for x in a)
    # the same multiset of shapes for every seed
    assert sorted(x.shape for x in a) == sorted(x.shape for x in c)


def test_closed_mix_shapes_follow_shares():
    tr = cells.traffic("closed32_img")
    shapes = traffic_gen.pool_shapes(tr, _shape(3))
    on_rung = sum(s == (224, 224, 3) for s in shapes)
    assert on_rung == 56
    assert all(160 <= h <= 320 and 160 <= w <= 320 for h, w, _ in shapes)


@pytest.mark.parametrize("seconds", [1.0, 10.0])
def test_open_schedule_same_work_every_seed(seconds):
    tr = cells.traffic("bursty_clip")
    runs = [traffic_gen.open_schedule(tr, seconds, s) for s in SEEDS]
    again = traffic_gen.open_schedule(tr, seconds, SEEDS[1])
    assert np.array_equal(runs[1], again)
    n = {len(r) for r in runs}
    assert len(n) == 1
    assert n.pop() == pytest.approx(tr["rate"] * seconds, rel=0.01)
    for r in runs:
        assert np.all(np.diff(r) >= 0)
        assert 0 <= r[0] and r[-1] < seconds
    # arrival events: bursts of the mix's size share one due time
    sizes = np.unique(runs[0], return_counts=True)[1]
    assert set(sizes) <= {1, tr["burst"]["size"]}
    assert not np.array_equal(runs[0], runs[2])
