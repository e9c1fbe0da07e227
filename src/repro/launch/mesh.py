"""Production mesh builders.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  512 chips as (pod=2, data=16, model=16) — the pod axis carries
pure data parallelism (and the no-sync/local-SGD outer axis), so the slow
cross-pod links only ever see gradient/param traffic, never per-layer TP
collectives.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int = 1, n_model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist — tests/benchmarks."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_serving_mesh(shards: int | None = None, axis: str = "batch") -> Mesh:
    """1-D mesh for the PPR serving runtime: the engine's ``(B, n)`` batch
    axis is sharded over it (embarrassingly parallel slot rows — see
    ``repro.serving.ppr_engine.shard_batch_step``).  ``min(shards, devices)``
    shards, all devices when ``shards`` is None; the engine requires
    ``slots`` divisible by the resulting axis size."""
    n_dev = jax.device_count()
    shards = n_dev if shards is None else max(1, min(int(shards), n_dev))
    return jax.make_mesh((shards,), (axis,), axis_types=(AxisType.Auto,))


def make_solver_mesh(p: int | None = None, axis: str = "data") -> Mesh:
    """1-D mesh for the distributed PageRank solvers (graph partitions
    sharded along ``axis``): ``min(p, devices)`` shards, all devices when
    ``p`` is None.  Same mesh the registry's ``distributed_*`` build fn uses,
    exposed here for callers driving :func:`repro.core.distributed_pagerank`
    directly at pod scale."""
    from repro.core.distributed import solver_mesh

    return solver_mesh(p, axis=axis)
