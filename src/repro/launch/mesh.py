"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_mini_mesh(*, multi_pod: bool = False, devices_per_axis: int = 2):
    """Reduced mesh for CI-scale dry-run tests (8 host devices)."""
    d = devices_per_axis
    shape = (2, d, d) if multi_pod else (d, d)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


# ------------------------------------------------------------ serving mesh --
def _local_devices_for(num_shards: int):
    """The local devices, checked against ``num_shards``: on an
    accelerator every shard needs a chip of its own, so asking for more
    shards than chips raises instead of co-locating slabs.  On the CPU
    (tests) devices are reused round-robin — the placement/routing logic
    is identical, only the physical spread shrinks."""
    local = jax.local_devices()
    if num_shards > len(local) and local[0].platform != "cpu":
        raise ValueError(
            f"{num_shards} shards need {num_shards} devices; this host "
            f"has {len(local)} {local[0].platform} device(s)")
    return local


def shard_devices(num_shards: int):
    """Device assignment for a sharded page pool: shard i's slab lives on
    local device i (round-robin reuse only on the CPU)."""
    local = _local_devices_for(int(num_shards))
    return [local[i % len(local)] for i in range(int(num_shards))]


def make_shard_mesh(num_shards: int):
    """1-D ``("shard",)`` mesh for sharded page-pool serving.  On the CPU
    the axis is clamped to the local device count (a 4-shard pool on one
    CPU is a 1-device mesh with all four slabs co-located); on an
    accelerator there is one chip per shard or a ValueError."""
    n = min(int(num_shards), len(_local_devices_for(int(num_shards))))
    return make_auto_mesh((max(1, n),), ("shard",))
