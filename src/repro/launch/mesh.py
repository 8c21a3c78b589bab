"""Production meshes. Importing this module never touches jax device state
— meshes are built inside functions only.

  * single pod:  (16, 16)        axes ("data", "model")          = 256 chips
  * multi pod:   (2, 16, 16)     axes ("pod", "data", "model")   = 512 chips

``pod`` is the slow-interconnect data-parallel axis (cross-pod DCN/optical);
``data`` is within-pod DP / FSDP; ``model`` is tensor/expert parallelism.
The same functions build arbitrary elastic sizes for train/elastic.py.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax


def _make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-propagated sharding)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh (elastic resizes, tests). Uses the first
    prod(shape) devices."""
    need = math.prod(shape)
    have = len(jax.devices())
    if need > have:
        raise ValueError(f"mesh {tuple(shape)} needs {need} devices, "
                         f"have {have}")
    return _make_mesh(tuple(shape), tuple(axes))


def make_serving_mesh(n_replicas: int):
    """Serving-mode mesh: one ``replica`` axis over n_replicas devices.

    Each replica holds a full ``ConvertedStack`` copy (the deployed
    integer artifact is small — that is the point of the recipe), so the
    only mesh axis is data-parallel over replicas: a big flush batch
    shards its rows across lanes via ``models.sharding
    .serving_constrain``. Raises when the host exposes fewer devices
    (use ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for CPU
    simulation, as the sharding subprocess tests do)."""
    return make_mesh((n_replicas,), ("replica",))


def replica_devices(n_replicas: int):
    """Device placement for n logical replica lanes, round-robin over
    ``jax.devices()``. Unlike ``make_serving_mesh`` this OVERSUBSCRIBES
    rather than raises when devices run short — on a 1-device CPU host
    every lane maps to the same device, which is exactly the
    host-device-simulation mode the serving tests and benchmarks run in
    (lanes stay logically distinct: own windows, own stats, own routing
    rank)."""
    devs = jax.devices()
    return [devs[i % len(devs)] for i in range(n_replicas)]


def batch_axes(mesh, mode: str = "fsdp_tp") -> Tuple[str, ...]:
    """Mesh axes the global batch shards over. In ``fsdp_pure`` mode the
    ``model`` axis carries data parallelism too (no TP)."""
    names = ("pod", "data", "model") if mode == "fsdp_pure"         else ("pod", "data")
    return tuple(a for a in names if a in mesh.axis_names)


def dp_degree(mesh, mode: str = "fsdp_tp") -> int:
    n = 1
    for a in batch_axes(mesh, mode):
        n *= mesh.devices.shape[mesh.axis_names.index(a)]
    return n
