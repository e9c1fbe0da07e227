"""The program's own tracing: spans on the profiler's clock, serving
counters, and the grid size of each Pallas kernel.

* :func:`span` — a host span in the profiler's trace
  (``jax.profiler.TraceAnnotation``); its keyword arguments, and any added
  with ``set_metadata`` before it closes, are the event's stats.  Spans nest
  as they are called.  With no profiler running a span costs about a
  microsecond and records nothing: whether the profiler runs is the only
  switch.
* :class:`ServingMetrics` — the serving runtime's counters (offered,
  admitted, completed, rejected, expired, cache hits/misses/evictions/
  invalidations, update batches) and its ``queue_depth`` gauge, as plain
  ints and running mean/max, so a long run stays O(1) in memory.
* :data:`GRID_STEPS` — the real tiles one call of each Pallas Gauss–Seidel
  kernel processes (pad tiles not counted), written beside
  :data:`LAYOUTS` by whoever hands the kernel its layout
  (:func:`repro.kernels.spmv.ops.pagerank_pallas`, the PPR engine), so a
  kernel's device time can be read per real tile, the pads' cost
  included.  A grid step runs ``tiles_per_step`` of them.
* :data:`LAYOUTS` — the tile layout each Pallas Gauss–Seidel kernel's
  operands were last built with (``block``, ``tile_cap``, ``tiles``: the
  real tiles, ``fill``, ``chosen``: picked by
  :func:`repro.kernels.spmv.ops.choose_layout` rather than passed,
  ``pad_tiles``: tiles that pad dst-block runs to whole groups, and
  ``tiles_per_step``: tiles a grid step runs); and
  under ``"distributed"`` the sharded layout
  :class:`repro.core.distributed.DistributedBundle` was last built with:
  ``p``, ``vp``, ``n_pad``, ``cap`` (edge slots per shard), ``segments``
  (partial-sum segments per shard), ``edges_per_shard`` (real edges),
  ``exchange_bytes_per_round`` (what each device receives in a round's
  all-gather), and the build's host seconds ``partition_s`` and
  ``place_s``.

Span names and their stats (``repro.serving``):

* ``ppr.offer``: ``qid``, ``outcome`` (cached / queued / rejected);
* ``ppr.admit``: ``qid``, ``slot``, ``warm``, ``queue_ms`` (pop − offer, on
  the runtime's clock);
* ``ppr.step``: ``step``, ``active``, ``slots``, ``queued``,
  ``active_after``; inside it ``ppr.dispatch`` (enqueueing the jitted
  ``multi_step``), ``ppr.sync`` (the blocking read of the per-sweep errors)
  and one ``ppr.harvest`` per converged slot (``qid``, ``sweeps``,
  ``converged_sweep``, ``warm``);
* ``ppr.cache_insert``: ``n``, the answers put in the result cache.

Span names and their stats (:mod:`repro.core.distributed`, the sharded
build):

* ``dist.build``: ``p``, ``vp``, ``cap``, ``edges_max``, ``edges_mean``;
  inside it ``dist.partition`` (bucketing and ordering the edges on the
  host, and their partial-sum segments) and ``dist.place`` (each shard's
  rows to its device, the vectors to every device; ``bytes_per_device``).

Inside each round of the distributed solvers, ``jax.named_scope``\\ s
mark the device work in the compiled program's operation names:
``dist.exchange`` (the rank vector's all-gather and the residual's
all-reduce), ``dist.dangling`` (the dangling mass) and ``dist.sweep``.  A
profiler trace's ``XLA Ops`` events are named by HLO instruction, so the
benchmark finds the collectives by their HLO kind (``all-gather``,
``all-reduce``) rather than by scope.
"""
from __future__ import annotations

import dataclasses

import jax

__all__ = ["GRID_STEPS", "Gauge", "LAYOUTS", "ServingMetrics", "span"]


def span(name: str, **args):
    """A context manager that records ``name`` with ``args`` as a host
    event of the profiler's trace while one runs."""
    return jax.profiler.TraceAnnotation(name, **args)


# kernel name -> real tiles per call, as last handed a layout
GRID_STEPS: dict[str, int] = {}
# kernel name -> the tile layout its operands were last built with
LAYOUTS: dict[str, dict] = {}


@dataclasses.dataclass
class Gauge:
    """Sampled level (queue depth): running mean/max."""

    count: int = 0
    total: float = 0.0
    max: float = 0.0

    def sample(self, value: float) -> None:
        self.count += 1
        self.total += value
        if value > self.max:
            self.max = value

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0


class ServingMetrics:
    """The serving runtime's counters and its ``queue_depth`` gauge,
    sampled once per pump."""

    def __init__(self) -> None:
        self.counters: dict[str, int] = {}
        self.gauges: dict[str, Gauge] = {"queue_depth": Gauge()}

    def incr(self, name: str, by: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + by

    def count(self, name: str) -> int:
        return self.counters.get(name, 0)

    def summary(self) -> str:
        """One-line human summary for launcher/benchmark stdout."""
        c = self.counters
        q = self.gauges["queue_depth"]
        parts = [
            f"offered={c.get('offered', 0)}",
            f"completed={c.get('completed', 0)}",
            f"rejected={c.get('rejected', 0)}",
            f"expired={c.get('expired', 0)}",
            f"cache_hits={c.get('cache_hits', 0)}",
            f"queue_depth mean={q.mean:.1f} max={q.max:.0f}",
        ]
        return "  ".join(parts)
