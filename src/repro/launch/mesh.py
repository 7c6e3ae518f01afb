"""Production mesh construction.

v5e pod = 256 chips → single-pod mesh (16, 16) with ("data", "model");
two pods → (2, 16, 16) with ("pod", "data", "model").  Defined as a
FUNCTION so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh_from_str", "batch_axes",
           "data_shards", "fleet_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh_from_str(spec: str):
    """e.g. "16x16" -> ("data","model"); "2x128" -> EP-style logical mesh
    over the same 256 chips (experts resident per model column, §Perf)."""
    dims = tuple(int(x) for x in spec.split("x"))
    axes = {2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    return jax.make_mesh(dims, axes)


def fleet_mesh(n_devices: int | None = None):
    """1-D ``("seeds",)`` mesh for sharding a fleet's seed axis.

    The co-simulator's batched engine treats one lane = one seed = one
    user; ``device_comm`` ``shard_map``s its chunk scan over this mesh
    (every in-scan op is per-lane, so shards never communicate).  Uses
    every visible device by default; CPU hosts get multiple devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return jax.make_mesh((n,), ("seeds",))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_shards(mesh) -> int:
    """Number of data-parallel shards (the coded-worker axis size)."""
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
