"""The serving mesh: a 1-D mesh whose axis shards the PPR engine's batch."""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_serving_mesh(shards: int | None = None, axis: str = "batch") -> Mesh:
    """1-D mesh for the PPR serving runtime: the engine's ``(B, n)`` batch
    axis is sharded over it (embarrassingly parallel slot rows — see
    ``repro.serving.ppr_engine.shard_batch_step``).  ``min(shards, devices)``
    shards, all devices when ``shards`` is None; the engine requires
    ``slots`` divisible by the resulting axis size."""
    n_dev = jax.device_count()
    shards = n_dev if shards is None else max(1, min(int(shards), n_dev))
    return jax.make_mesh((shards,), (axis,), axis_types=(AxisType.Auto,))
