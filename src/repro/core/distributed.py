"""Distributed PageRank over a device mesh (shard_map).

TPU adaptation of the paper's coordination schemes (DESIGN.md §2):

* ``barrier`` — one Jacobi sweep per global exchange. The per-round
  ``all_gather`` of the rank vector *is* the barrier: no device can start
  round ``t+1`` before every device published round ``t``. This is the
  faithful Alg-1 semantics at pod scale.

* ``stale``  — the No-Sync adaptation: each shard runs ``local_sweeps``
  Gauss–Seidel sweeps against its latest halo snapshot before the next
  exchange. Remote ranks are up to ``local_sweeps`` sweeps stale (the paper's
  staleness is unbounded-but-small; ours is bounded), local ranks are always
  fresh (the paper's single-``pr``-array effect). Collective traffic drops by
  ``local_sweeps`` while the fixed point is unchanged (Lemma 2).

* shard-level convergence — the TPU version of the paper's *thread-level*
  convergence: a shard whose residual is below threshold skips its sweep
  compute (masked) but keeps serving its frozen ranks to others.

All modes support ``handle_dangling``: the dangling-mass term is snapshotted
once per round from the freshly exchanged rank vector (the same
iteration-start semantics as ``_nosync_impl``'s prologue — Lemma 2: the fixed
point is stationary, so a bounded-staleness dangling snapshot leaves it
unchanged) and folded into every sweep's base term.  ``threshold=0`` runs
exactly ``max_iter`` rounds (LDBC Graphalytics' fixed-iteration PageRank).

**The layout** (:class:`DistributedBundle`).  Shard ``i`` owns the vertices
``[i·vp, (i+1)·vp)`` and the edges into them, ordered by destination and
padded to a common ``cap``; its rows live on device ``i`` only, while the
inverse out-degrees, the dangling mask and (on biased graphs) the bias are
replicated.  A shard's in-edges are summed in two float32 steps: runs of at
most :data:`CHUNK` consecutive edges of one destination into partials
(``seg``), then the partials into their vertex (``seg_dst``), so no float32
sum has more than ``max(CHUNK, ⌈in-degree/CHUNK⌉ + 1)`` terms — a vertex
with a million in-edges loses no more than one with a thousand.  Padding
edges carry the segment id ``n_seg``, which ``segment_sum`` drops: no mask.
The bundle's arrays are its ``jax.Array`` attributes, so a caller can
compile one solve with them as operands (``dataclasses.replace`` the
bundle with the traced arrays inside ``jax.jit``).

**The sharded build** (:meth:`DistributedBundle.build`) takes an
unsorted :class:`repro.graphs.csr.EdgeList`:
:func:`repro.core.pagerank.partition_edges` buckets and orders the edges on
host threads, each row goes straight to its device (``NamedSharding``), and
the out-degrees are counted on the devices (a ``psum`` of the shards'
counts), so no full-size array sits on one device or in float64 on the
host.  A :class:`repro.graphs.csr.Graph` (weights and bias included) goes
through :meth:`PartitionedGraph.from_graph` instead.

**Tracing** (:mod:`repro.utils.tracing`): the host spans ``dist.build``
(stats ``p``, ``vp``, ``cap``, ``edges_max``, ``edges_mean``) holding
``dist.partition`` and ``dist.place`` (``bytes_per_device``);
``LAYOUTS["distributed"]``; and the named scopes ``dist.exchange`` (the
all-gather and the residual's all-reduce), ``dist.dangling`` and
``dist.sweep`` inside each round.

The solvers are also **registry entries** (``distributed_barrier``,
``distributed_stale``, ``distributed_topk``): ``build`` makes a
:class:`DistributedBundle` on a 1-D mesh over however many devices exist,
capped by ``threads``, so the launcher, benchmarks, and the Lemma-2
round-trip tests cover the pod-scale modes exactly like the single-device
variants.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.pagerank import (
    DEFAULT_DAMPING, PageRankResult, PartitionedGraph, partition_edges)
from repro.core.solver import register_variant
from repro.graphs.csr import EdgeList
from repro.utils.tracing import LAYOUTS, span

# consecutive in-edges of one vertex summed into one float32 partial
CHUNK = 1024


@dataclasses.dataclass
class DistributedBundle:
    """Device bundle of the distributed variants: per-shard edge rows
    sharded over a 1-D mesh, the per-vertex vectors replicated (module
    docstring).  ``weight``/``bias`` are ``None`` on unweighted graphs."""

    n: int
    p: int
    vp: int  # vertices per shard
    mesh: Mesh
    # shard i's rows are elements [i·len, (i+1)·len) of each flat array
    src: jax.Array  # (p·cap,) int32 global source ids, 0 in padding
    seg: jax.Array  # (p·cap,) int32 partial-sum segment, n_seg in padding
    seg_dst: jax.Array  # (p·n_seg,) int32 local dst per segment, vp unused
    inv_out: jax.Array  # (n_pad,) 1/outdeg, 0 for dangling and padding
    dangling: jax.Array  # (n_pad,) 1 for out-degree-0 vertices < n
    weight: jax.Array | None = None  # (p·cap,) per-edge weight
    bias: jax.Array | None = None  # (n_pad,) teleport multiplier
    axis: str = "data"

    @property
    def n_pad(self) -> int:
        return self.p * self.vp

    @property
    def cap(self) -> int:
        return self.src.shape[0] // self.p

    @classmethod
    def build(cls, g, mesh: Mesh, axis: str = "data",
              dtype=jnp.float32) -> "DistributedBundle":
        """The bundle of ``g`` on ``mesh``, one shard per device of
        ``axis``: an :class:`EdgeList` through the sharded build, a
        ``Graph`` through :meth:`PartitionedGraph.from_graph`, or a
        ``PartitionedGraph`` with as many partitions as the axis has
        devices."""
        p = mesh.shape[axis]
        if isinstance(g, PartitionedGraph) and g.p != p:
            raise ValueError(f"graph partitions ({g.p}) != mesh axis size "
                             f"({p})")
        vp = max(1, -(-g.n // p))
        t0 = time.perf_counter()
        with span("dist.build") as sp:
            with span("dist.partition"):
                weight = bias = vectors = None
                if isinstance(g, EdgeList):
                    src, seg, counts = partition_edges(g.n, g.src, g.dst, p)
                else:
                    pg = (g if isinstance(g, PartitionedGraph) else
                          PartitionedGraph.from_graph(g, p, gain=False))
                    src, seg, counts, weight, bias, vectors = (
                        _partitioned_rows(pg))
                seg_dst = _chunk_segments(seg, counts, vp)
            t1 = time.perf_counter()
            sp.set_metadata(p=p, vp=vp, cap=int(src.shape[1]),
                            edges_max=int(counts.max()),
                            edges_mean=float(counts.mean()))
            rows = NamedSharding(mesh, P(axis))
            whole = NamedSharding(mesh, P())
            item = jnp.dtype(dtype).itemsize
            per_device = (src[0].nbytes + seg[0].nbytes + seg_dst[0].nbytes
                          + (weight is not None) * src.shape[1] * item
                          + (2 + (bias is not None)) * p * vp * item)
            with span("dist.place", bytes_per_device=per_device):
                src_d, seg_d, seg_dst_d = (_place(a, rows)
                                           for a in (src, seg, seg_dst))
                del src, seg
                weight_d = (None if weight is None
                            else _place(weight.astype(dtype), rows))
                bias_d = (None if bias is None
                          else _place(bias.astype(dtype), whole))
                if vectors is None:
                    inv_out, dangling = _degree_vectors(
                        src_d, seg_d, mesh, axis, n=g.n, n_pad=p * vp,
                        n_seg=seg_dst.shape[1], dtype=dtype)
                else:
                    inv_out, dangling = (_place(x.astype(dtype), whole)
                                         for x in vectors)
        LAYOUTS["distributed"] = {
            "p": p, "vp": vp, "n_pad": p * vp, "cap": src_d.shape[0] // p,
            "segments": int(seg_dst.shape[1]),
            "edges_per_shard": [int(c) for c in counts],
            # what each device receives in a round's all-gather
            "exchange_bytes_per_round": (p - 1) * vp * item,
            # host seconds: bucketing and ordering; to the devices
            "partition_s": t1 - t0, "place_s": time.perf_counter() - t1,
        }
        return cls(n=g.n, p=p, vp=vp, mesh=mesh, src=src_d, seg=seg_d,
                   seg_dst=seg_dst_d, inv_out=inv_out, dangling=dangling,
                   weight=weight_d, bias=bias_d, axis=axis)


def _partitioned_rows(pg: PartitionedGraph):
    """Host ``(src, local, counts, weight, bias, (inv_out, dangling))`` of
    a ``PartitionedGraph``, its padding marked as :func:`partition_edges`
    marks it (local destination ``vp``).  Its inverse out-degrees are kept:
    a contracted core's out-degrees count edges the plan pruned."""
    counts = (np.asarray(pg.emask) > 0).sum(axis=1).astype(np.int64)
    local = np.array(pg.dst_local, dtype=np.int32)
    local[np.arange(local.shape[1])[None, :] >= counts[:, None]] = pg.vp
    weight = None if pg.w_pad is None else np.asarray(pg.w_pad)
    bias = None if pg.bias_pad is None else np.asarray(pg.bias_pad)
    vectors = (np.asarray(pg.inv_out), np.asarray(pg.dangling))
    return np.asarray(pg.src_pad), local, counts, weight, bias, vectors


def _chunk_segments(local: np.ndarray, counts: np.ndarray, vp: int):
    """Turn the dst-ordered rows ``local`` into their segment ids, in
    place, and return ``seg_dst``.  A segment starts at every change of
    destination and every :data:`CHUNK` edges; padding gets the id ``n_seg``
    (one past the most any row has), unused ``seg_dst`` slots the
    destination ``vp``, and ``segment_sum`` drops both."""
    p, _ = local.shape

    def row(i):
        k = int(counts[i])
        r = local[i, :k]
        start = np.ones(k, bool)
        np.not_equal(r[1:], r[:-1], out=start[1:])
        start[::CHUNK] = True
        heads = r[start]
        np.cumsum(start, dtype=np.int32, out=r)
        r -= 1
        return heads

    with concurrent.futures.ThreadPoolExecutor(p) as pool:
        heads = list(pool.map(row, range(p)))
    n_seg = max(1, max(h.size for h in heads))
    seg_dst = np.full((p, n_seg), vp, np.int32)
    for i, h in enumerate(heads):
        local[i, int(counts[i]):] = n_seg
        seg_dst[i, :h.size] = h
    return seg_dst


def _place(host: np.ndarray, sharding: NamedSharding) -> jax.Array:
    """``host`` flattened, each device given its own slice and nothing
    else (a ``(p, k)`` array's row ``i`` goes to the axis' device ``i``)."""
    flat = host.reshape(-1)
    return jax.make_array_from_callback(flat.shape, sharding,
                                        lambda idx: flat[idx])


def _degree_vectors(src, seg, mesh, axis, *, n, n_pad, n_seg, dtype):
    """Replicated ``(inv_out, dangling)``: each device counts the out-edges
    of its shard's sources, a ``psum`` adds the counts."""
    def vectors(src, seg):
        real = (seg < n_seg).astype(jnp.int32)
        deg = jax.lax.psum(
            jax.ops.segment_sum(real, src, num_segments=n_pad), axis)
        inv_out = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1), 0.0)
        dangling = (deg == 0) & (jnp.arange(n_pad) < n)
        return inv_out.astype(dtype), dangling.astype(dtype)

    return jax.block_until_ready(jax.jit(jax.shard_map(
        vectors, mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=(P(), P()), check_vma=False))(src, seg))


def _sweep(pr_full, local, srcs, segs, seg_dst, weight, inv_out, base, d,
           vp, offset):
    """One Gauss–Seidel sweep of the local shard against ``pr_full``.

    ``base`` is the per-vertex additive term — scalar ``(1-d)/n`` (or the
    ``(vp,)`` bias-scaled vector on biased graphs) plus, when dangling mass
    is handled, this round's redistributed d·(dangling mass)/n.  In-edges
    are summed in two float32 steps (module docstring)."""
    pr_full = jax.lax.dynamic_update_slice_in_dim(pr_full, local, offset, 0)
    # every source id is in range: no wrap of negative ids, no clamp
    contrib = (pr_full * inv_out).at[srcs].get(
        mode="promise_in_bounds", wrap_negative_indices=False)
    if weight is not None:
        contrib = contrib * weight
    partial = jax.ops.segment_sum(contrib, segs, num_segments=seg_dst.size,
                                  indices_are_sorted=True)
    acc = jax.ops.segment_sum(partial, seg_dst, num_segments=vp,
                              indices_are_sorted=True)
    new = base + d * acc
    err = jnp.max(jnp.abs(new - local))
    return new, err


def _shard_map(b: DistributedBundle, solver):
    """``solver(src, seg, seg_dst, inv_out, dangling, weight, bias)`` on
    each shard (its own rows, the vectors whole), returning its local
    ranks and a scalar residual and round count."""
    ax = b.axis
    rows, whole = P(ax), P()
    weighted, biased = b.weight is not None, b.bias is not None

    def body(src, seg, seg_dst, inv_out, dangling, *rest):
        weight = rest[0] if weighted else None
        bias = rest[-1] if biased else None
        local, err, rounds = solver(src, seg, seg_dst, inv_out, dangling,
                                    weight, bias)
        return local, err[None], rounds[None]

    extra = (b.weight,) * weighted + (b.bias,) * biased
    mapped = jax.shard_map(
        body, mesh=b.mesh,
        in_specs=(rows, rows, rows, whole, whole)
        + (rows,) * weighted + (whole,) * biased,
        out_specs=(P(ax), P(ax), P(ax)), check_vma=False)
    pr, errs, rounds = jax.jit(mapped)(b.src, b.seg, b.seg_dst, b.inv_out,
                                       b.dangling, *extra)
    return PageRankResult(pr[:b.n], rounds[0], jnp.max(errs))


def _continue(threshold: float, max_rounds: int):
    """The round loop's condition on ``(err_global, rounds)``:
    ``threshold <= 0`` runs exactly ``max_rounds`` rounds."""
    if threshold <= 0:
        return lambda err, rounds: rounds < max_rounds
    return lambda err, rounds: (err > threshold) & (rounds < max_rounds)


def distributed_pagerank(
    graph,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    mode: str = "barrier",
    local_sweeps: int = 4,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_rounds: int = 10_000,
    shard_level_convergence: bool = False,
    handle_dangling: bool = False,
) -> PageRankResult:
    """Run PageRank on a :class:`DistributedBundle` (or on a
    ``PartitionedGraph`` sharded along ``axis`` of ``mesh``).

    Returns (pr[:n], rounds, err). ``rounds`` counts *global exchanges* —
    the paper's Fig-7 "iterations" comparison maps to rounds×sweeps for
    compute and rounds for synchronization.
    """
    if mode not in ("barrier", "stale"):
        raise ValueError(f"unknown mode {mode!r}")
    b = _as_bundle(graph, mesh, axis)
    n, vp, ax = b.n, b.vp, b.axis
    k = local_sweeps if mode == "stale" else 1
    dtype = b.inv_out.dtype
    base = jnp.asarray((1.0 - d) / n, dtype)
    thr = jnp.asarray(threshold, dtype)
    go_on = _continue(threshold, max_rounds)

    def solver(srcs, segs, seg_dst, inv_out, dangling, weight, bias):
        offset = jax.lax.axis_index(ax) * vp
        base_local = base if bias is None else base * jax.lax.dynamic_slice_in_dim(
            bias, offset, vp, 0)
        local0 = jnp.full((vp,), 1.0 / n, dtype)

        def round_body(state):
            local, err_local, _, rounds = state
            # exchange: gather the full rank vector (the barrier / halo snapshot)
            with jax.named_scope("dist.exchange"):
                pr_full = jax.lax.all_gather(local, ax, tiled=True)
            # dangling-mass snapshot at round start (iteration-start semantics,
            # one O(n) reduction per exchange; padding slots have dangling=0)
            with jax.named_scope("dist.dangling"):
                base_eff = base_local + (d * jnp.sum(pr_full * dangling) / n
                                         if handle_dangling else 0.0)

            def do_sweeps(local):
                # Convergence metric = FIRST sweep's residual (fresh-halo
                # Jacobi residual). Later sweeps iterate against the same
                # snapshot, so their shrinking residual reflects only local
                # convergence and would exit prematurely.
                def one(i, carry):
                    local, err = carry
                    new, err_s = _sweep(pr_full, local, srcs, segs, seg_dst,
                                        weight, inv_out, base_eff, d, vp,
                                        offset)
                    err = jnp.where(i == 0, err_s, err)
                    return new, err

                return jax.lax.fori_loop(0, k, one, (local, err_local))

            with jax.named_scope("dist.sweep"):
                if shard_level_convergence:
                    # CAUTION: skipping on the shard's own residual can freeze a
                    # shard whose inputs change later (the paper's No-Sync-Edge
                    # §4.4 failure mode, caught by the property tests) — and in
                    # lockstep SPMD it saves no wall-clock anyway. Off by default.
                    local, err_local = jax.lax.cond(
                        err_local > thr, do_sweeps, lambda l: (l, err_local),
                        local)
                else:
                    local, err_local = do_sweeps(local)
            with jax.named_scope("dist.exchange"):
                err_global = jax.lax.pmax(err_local, ax)
            return local, err_local, err_global, rounds + 1

        def round_cond(state):
            _, _, err_global, rounds = state
            return go_on(err_global, rounds)

        init = (local0, jnp.asarray(jnp.inf, dtype), jnp.asarray(jnp.inf, dtype),
                jnp.asarray(0, jnp.int32))
        local, _, err_global, rounds = jax.lax.while_loop(round_cond, round_body, init)
        return local, err_global, rounds

    # Note: stale-mode GS sweeps inside one round reuse the *same* snapshot
    # for remote ranks; pr_full is refreshed with fresh local ranks each sweep.
    return _shard_map(b, solver)


def distributed_pagerank_topk(
    graph,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    send_fraction: float = 0.125,
    local_sweeps: int = 2,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_rounds: int = 10_000,
    handle_dangling: bool = False,
) -> PageRankResult:
    """**Communication perforation** (beyond-paper, §Perf hillclimb #3).

    The paper perforates *computation* (skip near-converged vertices). At pod
    scale the analogous bottleneck is the exchange, so we perforate the
    *collective*: each round a shard publishes only its ``k = vp·fraction``
    largest rank *deltas* (index+value pairs) instead of the full vp-sized
    vector; unsent deltas stay in an error-feedback ledger and are published
    once they grow. Every shard folds the sparse updates into its own running
    snapshot of the global rank vector.

    Wire bytes per round: ``p·k·8`` vs ``p·vp·4`` — a 2/fraction reduction
    (4× at fraction=1/8, net of the index overhead). Fixed point unchanged:
    the ledger guarantees every delta is eventually published (same argument
    as Lemma 1/2 with bounded staleness).
    """
    b = _as_bundle(graph, mesh, axis)
    n, vp, n_pad, ax = b.n, b.vp, b.n_pad, b.axis
    k = max(1, int(vp * send_fraction))
    dtype = b.inv_out.dtype
    base = jnp.asarray((1.0 - d) / n, dtype)
    go_on = _continue(threshold, max_rounds)

    def solver(srcs, segs, seg_dst, inv_out, dangling, weight, bias):
        offset = jax.lax.axis_index(ax) * vp
        base_local = base if bias is None else base * jax.lax.dynamic_slice_in_dim(
            bias, offset, vp, 0)
        local0 = jnp.full((vp,), 1.0 / n, dtype)
        snap0 = jnp.full((n_pad,), 1.0 / n, dtype)
        sent0 = jnp.full((vp,), 1.0 / n, dtype)

        def round_body(state):
            local, snap, sent, err_local, _, rounds = state
            # 1. communication perforation: publish top-k deltas only
            with jax.named_scope("dist.exchange"):
                delta = local - sent
                _, top_idx = jax.lax.top_k(jnp.abs(delta), k)
                top_val = local[top_idx]
                sent = sent.at[top_idx].set(top_val)
                g_idx = jax.lax.all_gather(top_idx + offset, ax)  # (p,k)
                g_val = jax.lax.all_gather(top_val, ax)  # (p,k)
                snap = snap.at[g_idx.reshape(-1)].set(g_val.reshape(-1))

            # dangling-mass snapshot from the freshest local view (snapshot
            # with own fresh ranks folded in) — bounded staleness, fixed
            # point unchanged (Lemma 2)
            with jax.named_scope("dist.dangling"):
                if handle_dangling:
                    pr_eff = jax.lax.dynamic_update_slice_in_dim(
                        snap, local, offset, 0)
                    base_eff = base_local + d * jnp.sum(pr_eff * dangling) / n
                else:
                    base_eff = base_local

            # 2. local Gauss–Seidel sweeps against the snapshot
            def one(i, carry):
                loc, err = carry
                new, err_s = _sweep(snap, loc, srcs, segs, seg_dst, weight,
                                    inv_out, base_eff, d, vp, offset)
                err = jnp.where(i == 0, err_s, err)
                return new, err

            with jax.named_scope("dist.sweep"):
                local, err_local = jax.lax.fori_loop(
                    0, local_sweeps, one, (local, err_local))
            # residual must also cover unpublished deltas (ledger drain)
            resid = jnp.maximum(err_local, jnp.max(jnp.abs(local - sent)))
            with jax.named_scope("dist.exchange"):
                err_global = jax.lax.pmax(resid, ax)
            return local, snap, sent, err_local, err_global, rounds + 1

        def cond(state):
            *_, err_global, rounds = state
            return go_on(err_global, rounds)

        init = (local0, snap0, sent0, jnp.asarray(jnp.inf, dtype),
                jnp.asarray(jnp.inf, dtype), jnp.asarray(0, jnp.int32))
        local, _, _, _, err_global, rounds = jax.lax.while_loop(cond, round_body, init)
        return local, err_global, rounds

    return _shard_map(b, solver)


# ---------------------------------------------------------------------------
# Registry entries — DistributedBundle build + the three pod-scale modes
# ---------------------------------------------------------------------------


def _as_bundle(graph, mesh: Optional[Mesh], axis: str) -> DistributedBundle:
    if isinstance(graph, DistributedBundle):
        return graph
    if mesh is None:
        raise ValueError("a PartitionedGraph needs the mesh to shard it over")
    return DistributedBundle.build(graph, mesh, axis,
                                   dtype=graph.inv_out.dtype)


def solver_mesh(p: Optional[int] = None, axis: str = "data") -> Mesh:
    """1-D mesh for the distributed solvers: ``min(p, devices)`` shards (all
    devices when ``p`` is None).  The partition count must equal the mesh
    axis size, so the build fn derives ``p`` from this mesh — asking for 56
    partitions on a single-host run degrades gracefully instead of raising."""
    n_dev = jax.device_count()
    p = n_dev if p is None else max(1, min(int(p), n_dev))
    return jax.make_mesh((p,), (axis,), axis_types=(AxisType.Auto,))


def _dist_build(g, threads: int = 8, dtype=jnp.float32, **_) -> DistributedBundle:
    return DistributedBundle.build(g, solver_mesh(threads), dtype=dtype)


def _dist_run(mode: str):
    def run(b: DistributedBundle, *, d=DEFAULT_DAMPING, threshold=1e-8,
            max_iter=10_000, handle_dangling=False, local_sweeps=4, **_):
        return distributed_pagerank(
            b, mode=mode, local_sweeps=local_sweeps, d=d,
            threshold=threshold, max_rounds=max_iter,
            handle_dangling=handle_dangling,
        )

    return run


def _dist_topk_run(b: DistributedBundle, *, d=DEFAULT_DAMPING, threshold=1e-8,
                   max_iter=10_000, handle_dangling=False, local_sweeps=2,
                   send_fraction=0.125, **_):
    return distributed_pagerank_topk(
        b, send_fraction=send_fraction, local_sweeps=local_sweeps, d=d,
        threshold=threshold, max_rounds=max_iter,
        handle_dangling=handle_dangling,
    )


register_variant(
    "distributed_barrier", build=_dist_build, run=_dist_run("barrier"),
    description="shard_map Jacobi: one all-gather exchange per sweep (Alg 1 at pod scale)",
    layout="distributed", backend="shard_map", schedule="barrier",
)
register_variant(
    "distributed_stale", build=_dist_build, run=_dist_run("stale"),
    description="shard_map No-Sync: local_sweeps GS sweeps per exchange (bounded staleness)",
    layout="distributed", backend="shard_map", schedule="nosync",
)
register_variant(
    "distributed_topk", build=_dist_build, run=_dist_topk_run,
    description="communication perforation: top-k delta exchange + error-feedback ledger",
    layout="distributed", backend="shard_map", schedule="nosync",
)
