"""The one traffic generator: reads a traffic mix's JSON parameters.

A mix fixes the set of payload shapes and the amount of work; ``--seed``
only orders them and fills the payloads, so every seed offers the same
work in another order.

Keys of a mix file (``bench/traffic/<name>.json``):

* ``loop`` -- "closed" (``clients`` callers, each sending its next request
  when the last one is answered; ``ramp_requests`` answered before the
  window opens) or "open" (arrivals on a schedule at ``rate`` requests/s,
  whatever the system does);
* ``burst`` (open) -- ``{"p", "size"}``: an arrival event brings ``size``
  requests with probability ``p``, else one;
* ``pool`` -- distinct payloads per run; requests cycle through them;
* ``payload`` -- shares of payload shapes, each ``{"share", "spatial"}``
  with an inclusive ``[lo, hi]`` range per spatial dim; the trailing
  feature dim comes from the configuration;
* ``shape_seed`` -- fixes the drawn shapes, the same for every ``--seed``.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def _counts(shares, n):
    """Largest-remainder split of ``n`` by ``shares``."""
    raw = np.asarray(shares, float) / sum(shares) * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(counts - raw)[:n - counts.sum()]:
        counts[i] += 1
    return counts


def pool_shapes(traffic, payload_shape):
    """The mix's payload shapes: a fixed multiset, independent of --seed."""
    rng = np.random.default_rng(traffic["shape_seed"])
    mixes = traffic["payload"]
    shapes = []
    for mix, count in zip(mixes, _counts([m["share"] for m in mixes],
                                         traffic["pool"])):
        for _ in range(count):
            dims = [int(rng.integers(lo, hi + 1)) for lo, hi in mix["spatial"]]
            shapes.append(payload_shape(dims))
    return shapes


def payload_pool(traffic, payload_shape, seed):
    """The run's payloads: the fixed shapes in a seeded order, filled with
    seeded standard-normal float32 values."""
    shapes = pool_shapes(traffic, payload_shape)
    rng = _rng(seed, 0)
    return [rng.standard_normal(shapes[i], dtype=np.float32)
            for i in rng.permutation(len(shapes))]


def open_schedule(traffic, seconds, seed):
    """Due offsets (s, ascending, inside [0, seconds)) of an open loop's
    requests. Inter-arrival gaps are the quantiles of an exponential
    distribution (a Poisson process's), and a fixed number of events are
    bursts; the seed shuffles both, so each seed sends the same requests
    in another order."""
    burst = traffic["burst"]
    per_event = (1 - burst["p"]) + burst["p"] * burst["size"]
    n = max(1, round(traffic["rate"] * seconds / per_event))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    n_burst = round(burst["p"] * n)
    sizes = np.array([burst["size"]] * n_burst + [1] * (n - n_burst))
    rng = _rng(seed, 1)
    gaps, sizes = rng.permutation(gaps), rng.permutation(sizes)
    times = np.cumsum(gaps) * seconds / (gaps.sum() * (1 + 1 / n))
    return np.repeat(times, sizes)
