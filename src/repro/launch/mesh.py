"""Production mesh construction (function, not module-level constant, so
importing never touches jax device state).

Every mesh names its axis types: `jax.make_mesh` defaults to Explicit axes,
under which `with_sharding_constraint` and `shard_map`-ed programs that
expect automatic sharding propagation fail.  All meshes here are Auto."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_stream_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod ("data","model") or 2x16x16 ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis Auto (see module docstring)."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    return _auto_mesh((data, model), ("data", "model"))


def make_stream_mesh(n_shards: int = 1):
    """1-D ("data",) mesh for the sharded stream router
    (parallel/sharded_router.py): one shard of the key stream per device,
    loads synced by psum over "data" every load-sync epoch."""
    n_dev = jax.local_device_count()
    if n_shards > n_dev:
        raise ValueError(
            f"n_shards={n_shards} exceeds {n_dev} local device(s); "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU"
        )
    return _auto_mesh((n_shards,), ("data",))
