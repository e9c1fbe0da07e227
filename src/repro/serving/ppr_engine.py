"""Continuous-batching PPR query engine.

A host-side scheduler owns a fixed ``(B, n)`` device-resident batch of rank
rows (``B`` = ``slots``) and recycles its slots across queries; a jitted
multi-sweep step advances every active slot at once:

* **submit** — a seed query is allocated a free slot: its teleport row is
  written into the batch's teleport matrix and its rank row is initialized
  from the **warm cache** (the converged vector of an identical earlier
  query) or, cold, from the teleport row itself.
* **step** — one jitted call runs ``iters_per_step`` batched sweeps; frozen
  rows (free slots and already-converged ones) are held in place, which is
  the engine-level form of the batched solver's :func:`row_freeze` per-row
  early exit.  Each sweep's per-row change comes back with the state, so the
  scheduler sees convergence without an extra device round-trip; the last
  sweep's decides the harvest, the earlier ones say at which sweep a row
  first converged.
* **harvest** — a converged slot's row is pulled to host once, top-k
  extracted (ties broken by vertex id), the vector cached, and the slot
  recycled for the next queued query.

Two compute backends share the scheduler: ``"jax"`` drives the batched
vertex-centric sweep (:func:`repro.ppr.batched.make_batched_sweep`),
``"pallas"`` the multi-vector blocked Gauss–Seidel kernel
(:func:`repro.kernels.spmv.spmv_gs_pass_multi`) with the rank batch living
in VMEM across each pass.

Each step is a ``ppr.step`` span holding ``ppr.dispatch``, ``ppr.sync`` and
one ``ppr.harvest`` per converged slot (:mod:`repro.utils.tracing`).
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.pagerank import DeviceGraph
from repro.core.solver import DEFAULT_DAMPING
from repro.graphs.csr import Graph
from repro.kernels.spmv.kernel import multi_pass_tiles_per_step
from repro.kernels.spmv.ops import PallasGraph, TileLayout, choose_layout
from repro.ppr.batched import (
    bias_scaled,
    blocked_rows,
    make_batched_pallas_sweep,
    make_batched_sweep,
    teleport_from_seeds,
)
from repro.ppr.push import topk
from repro.utils.tracing import GRID_STEPS, LAYOUTS, span

__all__ = ["PPRQuery", "PPRResponse", "PPREngine", "make_query_stream",
           "shard_batch_step"]


@dataclasses.dataclass(frozen=True)
class PPRQuery:
    """One PPR request: rank the graph from ``seeds``' point of view.

    ``seeds`` is the teleport support (uniform over the set; duplicates are
    deduped — ``(3, 3, 5)`` and ``(3, 5)`` are the same query and share a
    cache entry); an empty tuple means a uniform teleport, i.e. the global
    PageRank question.  ``top_k`` bounds the answer size.  ``qid`` is the
    caller's correlation id, echoed verbatim on the response."""

    qid: int
    seeds: tuple[int, ...] = ()  # empty = uniform teleport (global query)
    top_k: int = 10


@dataclasses.dataclass
class PPRResponse:
    """A harvested answer: the converged slot's top-``k`` vertices.

    ``indices``/``values`` are rank-descending (ties broken by vertex id for
    determinism); ``iterations`` counts the sweeps charged to the slot at
    ``iters_per_step`` granularity, so it over-counts by at most one step;
    ``warm_start`` marks rows seeded from the LRU cache of converged
    vectors rather than from the teleport row."""

    qid: int
    seeds: tuple[int, ...]
    indices: np.ndarray  # (top_k,) vertex ids, rank-descending
    values: np.ndarray  # (top_k,) PPR estimates
    iterations: int  # sweeps charged to this slot (iters_per_step granular)
    latency_s: float  # submit → harvest wall time
    warm_start: bool  # row was seeded from the cache
    cached: bool = False  # answered from the runtime's top-k result cache


def make_query_stream(n: int, count: int, *, top_k: int = 10,
                      repeat_fraction: float = 0.25,
                      seed: int = 0) -> list[PPRQuery]:
    """Synthetic mixed PPR traffic — THE query stream for the serving demo
    and the serving benchmark (one generator, so they exercise the same
    mix): ~60% single-seed, ~25% multi-seed (2–4 seeds), ~15% uniform/global
    rows, with ``repeat_fraction`` of queries re-asking an earlier seed set
    (warm-cache traffic)."""
    rng = np.random.default_rng(seed)
    queries: list[PPRQuery] = []
    for i in range(count):
        if queries and rng.random() < repeat_fraction:
            seeds = queries[int(rng.integers(0, len(queries)))].seeds
        else:
            kind = rng.random()
            if kind < 0.60 or n < 2:  # tiny graphs can't host multi-seed
                seeds = (int(rng.integers(0, n)),)
            elif kind < 0.85:
                hi = min(4, n)  # seed-set size capped by the vertex count
                seeds = tuple(int(s) for s in
                              rng.choice(n, size=int(rng.integers(2, hi + 1)),
                                         replace=False))
            else:
                seeds = ()
        queries.append(PPRQuery(qid=i, seeds=seeds, top_k=top_k))
    return queries


@dataclasses.dataclass
class _Active:
    query: PPRQuery
    t0: float
    iters: int = 0
    warm: bool = False


class _Backend:
    """What both compute backends share: one engine step.  A backend's
    ``multi_step(pr, tele, frozen)`` runs ``iters_per_step`` sweeps and
    returns the state and each sweep's per-row change, ``(iters, B)``."""

    def step(self, frozen: np.ndarray) -> np.ndarray:
        with span("ppr.dispatch"):
            self.state, errs = self._multi_step(self.state, self.tele,
                                                jnp.asarray(frozen))
        with span("ppr.sync"):
            return np.asarray(errs)


class _JaxBackend(_Backend):
    """(B, n) rank batch advanced by the batched vertex-centric sweep."""

    BATCH_AXIS = 0  # slot axis of `state`/`tele` — the mesh-sharded axis

    def __init__(self, g: Graph, *, slots: int, d: float,
                 handle_dangling: bool, iters_per_step: int, **_):
        dg = DeviceGraph.from_graph(g)
        self.n = g.n
        sweep = make_batched_sweep(dg.src, dg.dst, dg.inv_out, dg.dangling,
                                   dg.weights,
                                   n=g.n, d=d, handle_dangling=handle_dangling)
        self.state = jnp.zeros((slots, g.n), jnp.float32)
        self.tele = jnp.zeros((slots, g.n), jnp.float32)

        def multi_step(pr, tele, frozen):
            def body(pr, _):
                new = jnp.where(frozen[:, None], pr, sweep(pr, tele))
                return new, jnp.max(jnp.abs(new - pr), axis=1)
            return jax.lax.scan(body, pr, length=iters_per_step)

        # unjitted: the mesh wrapper and the jaxpr lint both need the raw fn
        self.multi_step = multi_step
        self._multi_step = jax.jit(multi_step)

    def set_row(self, slot: int, row: np.ndarray, trow: np.ndarray) -> None:
        self.state = self.state.at[slot].set(jnp.asarray(row, jnp.float32))
        self.tele = self.tele.at[slot].set(jnp.asarray(trow, jnp.float32))

    def get_row(self, slot: int) -> np.ndarray:
        return np.asarray(self.state[slot], dtype=np.float64)


class _PallasBackend(_Backend):
    """(n_blocks, B, block) rank batch advanced by the multi-vector GS pass.

    With neither ``block`` nor ``tile_cap`` passed, the layout is the one
    :func:`repro.kernels.spmv.ops.choose_layout` picks from the graph's
    block-pair histogram; a passed one is honoured as given, the other
    taking its old default (256, 1024)."""

    BATCH_AXIS = 1  # slot axis of the (n_blocks, B, block) state

    def __init__(self, g: Graph, *, slots: int, d: float,
                 handle_dangling: bool, iters_per_step: int,
                 block: Optional[int] = None, tile_cap: Optional[int] = None,
                 interpret: Optional[bool] = None):
        chosen = block is None and tile_cap is None
        if chosen:
            block, tile_cap = choose_layout(g, rows=slots)
        pg = PallasGraph.build(g, block=256 if block is None else block,
                               tile_cap=1024 if tile_cap is None else tile_cap)
        self.n = g.n
        self.pg = pg
        self.layout = pg.layout._replace(
            chosen=chosen, tiles_per_step=multi_pass_tiles_per_step(
                pg.n_blocks, pg.block, pg.layout.tile_cap, slots))
        LAYOUTS["spmv_gs_pass_multi"] = self.layout._asdict()
        GRID_STEPS["spmv_gs_pass_multi"] = self.layout.tiles
        self.state = jnp.zeros((pg.n_blocks, slots, pg.block), jnp.float32)
        self.tele = jnp.zeros((pg.n_blocks, slots, pg.block), jnp.float32)
        sweep = make_batched_pallas_sweep(
            pg.tiles_src_local, pg.tiles_dst_local, pg.tiles_valid,
            pg.tile_src_block, pg.tile_dst_block, pg.inv_out_blocks,
            pg.dangling_blocks, pg.tiles_weight, n=g.n, block=pg.block, d=d,
            handle_dangling=handle_dangling, interpret=interpret)

        def multi_step(pr, tele, frozen):
            fz = frozen.astype(jnp.float32).reshape(1, -1)

            def body(pr, _):
                new = sweep(pr, tele, fz)
                return new, jnp.max(jnp.abs(new - pr), axis=(0, 2))
            return jax.lax.scan(body, pr, length=iters_per_step)

        # unjitted: the mesh wrapper and the jaxpr lint both need the raw fn
        self.multi_step = multi_step
        self._multi_step = jax.jit(multi_step)

    def set_row(self, slot: int, row: np.ndarray, trow: np.ndarray) -> None:
        rb = jnp.asarray(blocked_rows(row[None], self.pg.n_blocks,
                                      self.pg.block)[:, 0, :])
        tb = jnp.asarray(blocked_rows(trow[None], self.pg.n_blocks,
                                      self.pg.block)[:, 0, :])
        self.state = self.state.at[:, slot, :].set(rb)
        self.tele = self.tele.at[:, slot, :].set(tb)

    def get_row(self, slot: int) -> np.ndarray:
        return np.asarray(self.state[:, slot, :],
                          dtype=np.float64).reshape(-1)[:self.n]


_BACKENDS = {"jax": _JaxBackend, "pallas": _PallasBackend}


def shard_batch_step(backend, mesh: Mesh, axis: Optional[str] = None):
    """Re-jit ``backend``'s multi-step with the slot axis sharded over a 1-D
    ``mesh`` (``launch/mesh.py::make_serving_mesh``).

    Batch rows are independent solves — embarrassingly parallel — so the
    shard_map body is the backend's own ``multi_step`` unchanged: each device
    runs the identical sweep on its slice of slots and no collective ever
    runs inside the solve loop (the graph operands close over as replicated
    constants, the same discipline as ``repro.core.distributed``).  On a
    1-device mesh the mapped program IS the unsharded program, so the
    single-device path stays bit-identical — the serving tests assert exact
    top-k equality between the two."""
    axis = mesh.axis_names[0] if axis is None else axis
    bax = backend.BATCH_AXIS
    nd = backend.state.ndim
    spec = P(*[axis if i == bax else None for i in range(nd)])
    mapped = jax.shard_map(
        backend.multi_step, mesh=mesh,
        in_specs=(spec, spec, P(axis)),
        out_specs=(spec, P(None, axis)),
        check_vma=False,
    )
    backend._multi_step = jax.jit(mapped)
    return backend


class PPREngine:
    """Continuous-batching PPR serving over ``slots`` fixed batch rows.

    Lifecycle: :meth:`submit` admits a validated query into a free slot
    (warm-starting from the LRU cache when the same seed set converged
    before), :meth:`step` advances every active slot ``iters_per_step``
    sweeps in one jitted call and harvests/recycles the converged ones,
    :meth:`drain` runs a whole query list to completion.  ``backend`` picks
    the compute path (``"jax"`` batched vertex-centric sweep or ``"pallas"``
    multi-vector blocked GS kernel — see docs/KERNELS.md); both honour
    weighted/biased graphs, the bias folding into each teleport row at
    submit time.  ``backend_opts`` pass through to the backend (``block``,
    ``tile_cap``, ``interpret`` for pallas; without a layout the pallas
    backend chooses one from the graph, see :attr:`layout`)."""

    def __init__(self, g: Graph, *, slots: int = 8, d: float = DEFAULT_DAMPING,
                 threshold: float = 1e-7, handle_dangling: bool = False,
                 backend: str = "jax", iters_per_step: int = 8,
                 cache_size: int = 256, mesh: Optional[Mesh] = None,
                 **backend_opts):
        if backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {sorted(_BACKENDS)}, "
                             f"got {backend!r}")
        if g.n == 0:
            raise ValueError("cannot serve PPR over an empty graph")
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(f"serving mesh must be 1-D, got axes "
                                 f"{mesh.axis_names}")
            shards = mesh.shape[mesh.axis_names[0]]
            if slots % shards:
                raise ValueError(
                    f"slots ({slots}) must be divisible by the mesh axis "
                    f"size ({shards}) — each device owns slots/shards rows")
        self.g = g
        self.slots = slots
        self.d = d
        self.threshold = threshold
        self.handle_dangling = handle_dangling
        self.iters_per_step = iters_per_step
        self.backend_name = backend
        self.backend_opts = dict(backend_opts)
        self.mesh = mesh
        self._backend = self._make_backend(g)
        self._active: list[Optional[_Active]] = [None] * slots
        # free slots stay frozen: their rows are held in place by the sweep
        self._frozen = np.ones(slots, dtype=bool)
        self._cache: OrderedDict[tuple, np.ndarray] = OrderedDict()
        self._cache_size = cache_size
        self.warm_hits = 0
        # occupancy/backpressure observability (satellite of the serving
        # runtime): how often submit bounced off a full batch, and how many
        # slot·steps were actually busy vs available
        self.submit_rejections = 0
        self.busy_slot_steps = 0
        self.total_slot_steps = 0
        # queries waiting to take a slot as one frees: set by the serving
        # runtime around its steps, recorded on each step's span
        self.queued = 0
        # fired with the GraphDelta after every applied update batch — the
        # serving runtime hangs its result-cache invalidation here
        self.update_callbacks: list = []

    def _make_backend(self, g: Graph):
        backend = _BACKENDS[self.backend_name](
            g, slots=self.slots, d=self.d,
            handle_dangling=self.handle_dangling,
            iters_per_step=self.iters_per_step, **self.backend_opts)
        if self.mesh is not None:
            backend = shard_batch_step(backend, self.mesh)
        return backend

    @property
    def cache_block(self) -> int:
        """Invalidation granularity: the blocked-COO dst-block width the
        compute backend is tiled on (pallas), or the configured/default
        block for the un-tiled jax backend — the same width
        ``GraphDelta.touched_dst_blocks`` is quoted in."""
        return getattr(getattr(self._backend, "pg", None), "block",
                       self.backend_opts.get("block", 256))

    @property
    def layout(self) -> Optional[TileLayout]:
        """The Pallas backend's tile layout (``None`` for the jax backend):
        ``block``, ``tile_cap``, ``tiles``, ``fill`` and whether it was
        chosen from the graph or passed."""
        return getattr(self._backend, "layout", None)

    @property
    def slot_occupancy(self) -> float:
        """Busy fraction of the batch over every step so far (0 when the
        engine never stepped)."""
        if not self.total_slot_steps:
            return 0.0
        return self.busy_slot_steps / self.total_slot_steps

    # -- scheduling ---------------------------------------------------------

    def _cache_key(self, q: PPRQuery) -> tuple:
        return tuple(sorted(set(int(s) for s in q.seeds)))

    def validate(self, q: PPRQuery) -> None:
        """Raise for a malformed query — called BEFORE any engine state is
        touched, so a bad query can never leak a half-allocated slot."""
        for s in q.seeds:
            if not 0 <= int(s) < self.g.n:
                raise ValueError(
                    f"query {q.qid}: seed vertex {int(s)} out of range "
                    f"[0, {self.g.n})")

    def submit(self, q: PPRQuery) -> bool:
        """Admit ``q`` into a free slot; False when the batch is full.
        Raises on malformed seeds without mutating engine state."""
        self.validate(q)
        slot = self.free_slot()
        if slot is None:
            self.submit_rejections += 1
            return False
        # the subsystem-wide bias convention (repro.ppr.batched.bias_scaled):
        # a vertex bias scales the teleport row, t_eff = t·bias
        trow = bias_scaled(
            teleport_from_seeds([tuple(q.seeds)], self.g.n)[0], self.g.bias)
        cached = self._cache.get(self._cache_key(q))
        warm = cached is not None
        if warm:
            self._cache.move_to_end(self._cache_key(q))
            self.warm_hits += 1
        row = cached if warm else trow
        self._backend.set_row(slot, np.asarray(row, np.float64), trow)
        self._active[slot] = _Active(query=q, t0=time.perf_counter(), warm=warm)
        self._frozen[slot] = False
        return True

    def free_slot(self) -> Optional[int]:
        """The slot the next :meth:`submit` takes; None when the batch is
        full."""
        try:
            return self._active.index(None)
        except ValueError:
            return None

    def step(self) -> list[PPRResponse]:
        """Advance every active slot ``iters_per_step`` sweeps; harvest and
        recycle the slots that converged."""
        if all(a is None for a in self._active):
            return []
        active = self.active_count
        with span("ppr.step", step=self.total_slot_steps // self.slots,
                  active=active, slots=self.slots, queued=self.queued) as sp:
            self.busy_slot_steps += active
            self.total_slot_steps += self.slots
            errs = self._backend.step(self._frozen)
            out = self._harvest(errs)
            sp.set_metadata(active_after=self.active_count)
        return out

    def _harvest(self, errs: np.ndarray) -> list[PPRResponse]:
        """Harvest the slots whose last sweep changed no score by more than
        the threshold; ``errs`` is each sweep's per-row change."""
        out: list[PPRResponse] = []
        for slot, act in enumerate(self._active):
            if act is None:
                continue
            sweeps_before = act.iters
            act.iters += self.iters_per_step
            if errs[-1, slot] > self.threshold:
                continue
            # the first sweep of this step after which the row had converged
            converged = sweeps_before + 1 + int(
                np.argmax(errs[:, slot] <= self.threshold))
            with span("ppr.harvest", qid=act.query.qid, sweeps=act.iters,
                      converged_sweep=converged, warm=act.warm):
                row = self._backend.get_row(slot)
                idx, vals = topk(row, act.query.top_k)
            key = self._cache_key(act.query)
            self._cache[key] = row
            self._cache.move_to_end(key)
            while len(self._cache) > self._cache_size:
                self._cache.popitem(last=False)
            out.append(PPRResponse(
                qid=act.query.qid, seeds=tuple(act.query.seeds),
                indices=idx, values=vals, iterations=act.iters,
                latency_s=time.perf_counter() - act.t0,
                warm_start=act.warm))
            self._active[slot] = None
            self._frozen[slot] = True
        return out

    @property
    def active_count(self) -> int:
        return sum(a is not None for a in self._active)

    # -- dynamic updates ----------------------------------------------------

    def apply_updates(self, adds=None, dels=None, add_weights=None):
        """Apply an edge batch between queries: swap in the updated graph,
        rebuild the compute backend, and selectively invalidate the warm
        cache.  Returns the :class:`repro.graphs.csr.GraphDelta`.

        The engine must be idle (no active slots) — in-flight rank rows
        belong to the old graph's fixed points.  Cache rows are only warm
        *starts* (every admitted query still iterates to convergence), so
        invalidation is a latency heuristic, not a correctness one: rows
        whose seed set intersects an updated dst block (the blocked-COO
        granularity the backends are tiled on) are dropped, as is the
        empty-seed global row — a structural change anywhere perturbs the
        global fixed point."""
        if self.active_count:
            raise RuntimeError(
                "cannot apply updates with active slots; drain first")
        g_new, delta = self.g.apply_updates(adds=adds, dels=dels,
                                            add_weights=add_weights)
        if delta.num_ops:
            self.g = g_new
            self._backend = self._make_backend(g_new)
            block = self.cache_block
            hot = set((delta.touched_vertices() // block).tolist())
            stale = [k for k in self._cache
                     if not k or any(s // block in hot for s in k)]
            for k in stale:
                del self._cache[k]
            for cb in self.update_callbacks:
                cb(delta)
        return delta

    def reset(self) -> None:
        """Forget the warm cache and counters (engine must be idle) — lets a
        benchmark reuse one engine (and its already-traced jitted step) for a
        cold measured run; re-jitting a fresh engine would put compile time
        inside the timed region."""
        if self.active_count:
            raise RuntimeError("cannot reset a PPREngine with active slots")
        self._cache.clear()
        self.warm_hits = 0
        self.submit_rejections = 0
        self.busy_slot_steps = 0
        self.total_slot_steps = 0

    def drain(self, queries, max_steps: int = 100_000) -> list[PPRResponse]:
        """Feed ``queries`` through the engine (admitting as slots free up)
        and run until every response is harvested.

        The whole batch is validated up front: one malformed query raises
        BEFORE any work starts, instead of aborting mid-drain and discarding
        the responses already harvested."""
        queries = list(queries)
        for q in queries:
            self.validate(q)
        pending = deque(queries)
        out: list[PPRResponse] = []
        steps = 0
        while pending or self.active_count:
            while pending and self.submit(pending[0]):
                pending.popleft()
            out += self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"PPREngine.drain did not converge within {max_steps} "
                    f"steps (threshold={self.threshold})")
        return out
