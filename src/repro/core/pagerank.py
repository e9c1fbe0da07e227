"""PageRank variants from the paper, as declarative entries on the shared
convergence engine (:mod:`repro.core.solver`).

Engine/registry layout — each variant is a **sweep** (how Eq. (1) is applied)
plus a **schedule** (``barrier`` = Jacobi, ``nosync`` = in-iteration fresh
reads) plus optional **transforms** (Alg 5 perforation); the single
``jax.lax.while_loop`` lives in :func:`repro.core.solver.solve`.

Variant map (paper §4 → registry name → composition):

* ``barrier``           — Alg 1: vertex-centric sweep, barrier schedule.
* ``barrier_edge``      — Alg 2: 3-phase edge-centric sweep (phase I scatters
                          per-edge contributions through ``offsetList``,
                          phase II gathers/segment-sums), barrier schedule.
* ``barrier_opt``       — Alg 1 + perforation transform.
* ``barrier_identical`` — STIC-D identical-node sweep (vertices with equal
                          in-neighbour sets share one computation), barrier.
* ``nosync``            — Alg 3: partition sweep on the nosync schedule —
                          partitions swept sequentially *within* an iteration,
                          each reading the freshest ranks (single pr array); a
                          deterministic member of the admissible async
                          executions (Lemma 2: fixed point is schedule-
                          independent).  ``thread_level`` termination per
                          Alg 3 l.17-19 is the schedule's observed-error skip.
* ``nosync_opt``        — Alg 3 + Alg 5 perforation transform.
* ``nosync_adaptive``   — Alg 3 on the residual-adaptive schedule: partitions
                          swept in descending residual order, partitions whose
                          certified residual bound is at or below tolerance
                          skipped outright (staleness kept sound by the
                          cross-partition gain matrix — docs/SCHEDULING.md).
* ``pallas``/``pallas_nosync``/``pallas_nosync_opt`` — the blocked Pallas
                          SpMV sweep on either schedule (plus the perforated
                          fresh-read form); registered from
                          ``repro.kernels.spmv.ops``.
* ``barrier_sticd``/``nosync_sticd`` — the full STIC-D decomposition
                          (identical rewiring + chain/dead pruning,
                          ``repro.graphs.csr.DecompositionPlan``) as a build-
                          time plan stage in front of the Alg-1/Alg-3 core
                          solve; ranks of pruned vertices are reconstructed
                          after convergence (``solver.plan_run``).
* ``distributed_barrier``/``distributed_stale``/``distributed_topk`` — the
                          shard_map pod-scale modes; registered from
                          ``repro.core.distributed``.

Every variant accepts ``handle_dangling`` and, when set, converges to the
same dangling-redistributed fixed point as :func:`pagerank_numpy` (the
sequential oracle) — the registry round-trip tests assert this (Lemma 2).

Every variant also honours **weighted/biased graphs** (optional per-edge
``Graph.weights`` scaling each contribution, optional per-vertex
``Graph.bias`` multiplying the teleport base) — the representation the
STIC-D plan's mid-graph chain contraction produces, validated against the
weighted :func:`pagerank_numpy` oracle by tests/test_weighted.py.
Unweighted graphs (``weights=None``/``bias=None``) trace to the exact
pre-weighted computation — no extra multiplies.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solver import (
    DEFAULT_DAMPING,
    PageRankResult,
    adaptive_schedule,
    barrier_schedule,
    nosync_schedule,
    perforation,
    plan_build,
    plan_run,
    register_variant,
    solve,
)
from repro.graphs.csr import Graph, inv_out_and_dangling

__all__ = [
    "DEFAULT_DAMPING",
    "PageRankResult",
    "DeviceGraph",
    "EdgeCentricGraph",
    "PartitionedGraph",
    "partition_edges",
    "IdenticalNodePlan",
    "pagerank_numpy",
    "l1_norm",
    "pagerank_barrier",
    "pagerank_barrier_edge",
    "pagerank_barrier_opt",
    "pagerank_nosync",
    "pagerank_nosync_adaptive",
    "pagerank_identical",
    "partition_gain_matrix",
    "vertex_gain_matrix",
]


# ---------------------------------------------------------------------------
# Device-side graph bundles
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DeviceGraph:
    """dst-sorted COO on device + degree info (vertex-centric variants).

    ``weights``/``bias`` mirror the host graph's optional per-edge weights
    and per-vertex teleport-bias multiplier (``None`` = unweighted fast
    path — the sweeps skip the extra multiplies entirely)."""

    n: int
    src: jax.Array  # (m,) int32 — sorted by dst
    dst: jax.Array  # (m,) int32
    inv_out: jax.Array  # (n,) — 1/outdeg, 0 for dangling
    dangling: jax.Array  # (n,) float mask of outdeg==0 vertices
    weights: jax.Array | None = None  # (m,) per-edge weight, dst-sorted
    bias: jax.Array | None = None  # (n,) base multiplier

    @classmethod
    def from_graph(cls, g: Graph, dtype=jnp.float32) -> "DeviceGraph":
        inv, dang = inv_out_and_dangling(g.out_degree)
        return cls(
            n=g.n,
            src=jnp.asarray(g.src),
            dst=jnp.asarray(g.dst),
            inv_out=jnp.asarray(inv, dtype=dtype),
            dangling=jnp.asarray(dang, dtype=dtype),
            weights=(None if g.weights is None
                     else jnp.asarray(g.weights, dtype=dtype)),
            bias=None if g.bias is None else jnp.asarray(g.bias, dtype=dtype),
        )


@dataclasses.dataclass
class EdgeCentricGraph:
    """Alg-2 layout: out-CSR scatter slots (``offsetList``) + dst order.

    Per-edge weights stay in dst-sorted order: phase II scales the gathered
    contribution list, which is equivalent to weighting at scatter time but
    keeps phase I a pure permutation."""

    n: int
    m: int
    src_by_src: jax.Array  # (m,) int32 — edges in src-sorted order
    edge_slot: jax.Array  # (m,) int64 — offsetList: slot in dst-sorted order
    dst: jax.Array  # (m,) int32 — dst-sorted order (phase II)
    inv_out: jax.Array
    dangling: jax.Array
    weights: jax.Array | None = None  # (m,) dst-sorted per-edge weight
    bias: jax.Array | None = None  # (n,) base multiplier

    @classmethod
    def from_graph(cls, g: Graph, dtype=jnp.float32) -> "EdgeCentricGraph":
        out_ptr, _, edge_slot = g.out_csr()
        # src id per edge in src-sorted order
        src_ids = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(out_ptr))
        inv, dang = inv_out_and_dangling(g.out_degree)
        return cls(
            n=g.n,
            m=g.m,
            src_by_src=jnp.asarray(src_ids),
            edge_slot=jnp.asarray(edge_slot),
            dst=jnp.asarray(g.dst),
            inv_out=jnp.asarray(inv, dtype=dtype),
            dangling=jnp.asarray(dang, dtype=dtype),
            weights=(None if g.weights is None
                     else jnp.asarray(g.weights, dtype=dtype)),
            bias=None if g.bias is None else jnp.asarray(g.bias, dtype=dtype),
        )


def partition_gain_matrix(g: Graph, unit: int, p: int) -> np.ndarray:
    """Cross-unit max-norm gain matrix of one PageRank sweep,

        G[i, j] = max_{v in unit i}  Σ_{u in unit j, (u,v) ∈ E}  w_uv/outdeg_u ,

    for the contiguous unit layout ``unit i = vertices [i·unit, (i+1)·unit)``
    (partitions of :class:`PartitionedGraph`, dst blocks of the Pallas
    layout).  This is the static certificate behind the adaptive schedules:
    if every rank in unit ``j`` moved by at most ``Δ_j`` this round, a fresh
    sweep of unit ``i`` can move any of its ranks by at most
    ``d·Σ_j G[i,j]·Δ_j`` — so a skipped unit's residual bound inflated by
    that amount stays a true bound (``repro.core.solver.adaptive_schedule``).
    Callers add the dangling-redistribution term (``|dangling ∩ j|/n`` per
    column) when running with ``handle_dangling``.

    Host-side, O(m log m), float64 accumulation; dense ``(p, p)`` output —
    fine for thread-scale ``p``, quadratic in block count for the blocked
    layout (which is why the Pallas build computes it only on request).
    """
    src = np.asarray(g.src)
    dst = np.asarray(g.dst).astype(np.int64)
    out_degree = np.asarray(g.out_degree)
    inv_out = np.where(out_degree > 0, 1.0 / np.maximum(out_degree, 1), 0.0)
    vals = inv_out[src]
    if g.weights is not None:
        vals = vals * np.asarray(g.weights)
    gain = np.zeros((p, p), dtype=np.float64)
    if src.size:
        # per-(dst vertex, src unit) sums, then a max-reduce over each
        # dst unit's vertices
        keys = dst * p + (src.astype(np.int64) // unit)
        uniq, inv_idx = np.unique(keys, return_inverse=True)
        sums = np.bincount(inv_idx, weights=np.abs(vals), minlength=uniq.size)
        np.maximum.at(gain, ((uniq // p) // unit, uniq % p), sums)
    return gain


def vertex_gain_matrix(g: Graph, unit: int, p: int, n_pad: int) -> np.ndarray:
    """Per-**vertex** cross-unit gain operator of one PageRank sweep,

        S[v, j] = Σ_{u in unit j, (u,v) ∈ E}  |w_uv|/outdeg_u ,

    shape ``(n_pad, p)`` — the row-resolved refinement of
    :func:`partition_gain_matrix` (which max-reduces S's rows over each dst
    unit).  The adaptive schedule carries a per-vertex residual bound and
    inflates it by ``d·S@Δ``; the partition skip decision then takes the max
    over member rows *after* accumulation, which is much tighter than
    inflating with the pre-maxed ``(p, p)`` certificate: one hub vertex in a
    partition no longer forces the whole partition's bound to absorb every
    neighbour's delta.  In the prototype this is the difference between
    breaking even with nosync and 25–45% fewer sweeps.

    Dense ``(n_pad, p)`` float64 host-side — linear in ``n·p``, which is
    fine at thread-scale ``p`` but is exactly why the blocked Pallas layout
    (``p`` = thousands of blocks) sticks with the ``(p, p)`` certificate.
    Callers add the dangling-redistribution term (``|dangling ∩ j|/n`` per
    column) when running with ``handle_dangling``.
    """
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    out_degree = np.asarray(g.out_degree)
    inv_out = np.where(out_degree > 0, 1.0 / np.maximum(out_degree, 1), 0.0)
    vals = inv_out[src]
    if g.weights is not None:
        vals = vals * np.asarray(g.weights)
    s = np.zeros((n_pad, p), dtype=np.float64)
    if src.size:
        np.add.at(s, (dst, src // unit), np.abs(vals))
    return s


@dataclasses.dataclass
class PartitionedGraph:
    """Static vertex partitions with padded per-partition edge lists.

    This is the paper's static load allocation (§4.1) made SPMD-friendly:
    every partition owns ``vp`` contiguous vertices and a fixed-capacity edge
    buffer (padded), so a ``fori_loop``/``shard_map`` over partitions has
    static shapes.
    """

    n: int
    p: int
    vp: int  # vertices per partition
    n_pad: int
    src_pad: jax.Array  # (p, cap) int32 global src ids (0 where invalid)
    dst_local: jax.Array  # (p, cap) int32 local dst ids in [0, vp)
    emask: jax.Array  # (p, cap) dtype — 1 for real edges
    inv_out: jax.Array  # (n_pad,)
    dangling: jax.Array  # (n_pad,)
    w_pad: jax.Array | None = None  # (p, cap) per-edge weight (0 = padding)
    bias_pad: jax.Array | None = None  # (n_pad,) base multiplier (0 padding)
    gain: jax.Array | None = None  # (n_pad, p) per-vertex sweep gain

    @property
    def edge_mult(self) -> jax.Array:
        """Effective per-edge multiplier: weights when present, else the
        {0,1} validity mask — sweeps multiply by exactly one of the two, so
        the unweighted path pays nothing extra."""
        return self.emask if self.w_pad is None else self.w_pad

    @classmethod
    def from_graph(cls, g: Graph, p: int, dtype=jnp.float32,
                   gain: bool = True) -> "PartitionedGraph":
        """``gain=False`` leaves out the ``(n_pad, p)`` gain matrix, which
        only the adaptive schedule reads."""
        vp = -(-g.n // p)
        n_pad = vp * p
        bounds = np.arange(p + 1) * vp
        e_bounds = np.searchsorted(g.dst, bounds)
        cap = max(1, int(np.max(np.diff(e_bounds))))
        src_pad = np.zeros((p, cap), dtype=np.int32)
        dst_local = np.zeros((p, cap), dtype=np.int32)
        emask = np.zeros((p, cap), dtype=np.float64)
        w_pad = np.zeros((p, cap), dtype=np.float64) if g.weights is not None else None
        for i in range(p):
            e0, e1 = e_bounds[i], e_bounds[i + 1]
            k = e1 - e0
            src_pad[i, :k] = g.src[e0:e1]
            dst_local[i, :k] = g.dst[e0:e1] - i * vp
            emask[i, :k] = 1.0
            if w_pad is not None:
                w_pad[i, :k] = g.weights[e0:e1]
        inv, dang = inv_out_and_dangling(g.out_degree, n_pad)
        bias_pad = None
        if g.bias is not None:
            bias_pad = np.zeros(n_pad, dtype=np.float64)
            bias_pad[:g.n] = g.bias
        return cls(
            n=g.n,
            p=p,
            vp=vp,
            n_pad=n_pad,
            src_pad=jnp.asarray(src_pad),
            dst_local=jnp.asarray(dst_local),
            emask=jnp.asarray(emask, dtype=dtype),
            inv_out=jnp.asarray(inv, dtype=dtype),
            dangling=jnp.asarray(dang, dtype=dtype),
            w_pad=None if w_pad is None else jnp.asarray(w_pad, dtype=dtype),
            bias_pad=(None if bias_pad is None
                      else jnp.asarray(bias_pad, dtype=dtype)),
            # p is thread-scale, so the (n_pad, p) vertex-gain certificate
            # costs about one extra rank-vector per partition — cheap enough
            # to carry by default, so every partitioned bundle can run the
            # adaptive schedule without a rebuild
            gain=(jnp.asarray(vertex_gain_matrix(g, vp, p, n_pad), dtype=dtype)
                  if gain else None),
        )


# 16-bit digits: numpy's stable argsort of a uint16 key is a radix sort
_DIGIT = 16


def partition_edges(n: int, src, dst, p: int, *, workers: int | None = None):
    """Bucket an unsorted edge list by owning partition and order each
    partition by local destination, with no global sort and no float array.

    Partition ``i`` owns the vertices ``[i·vp, (i+1)·vp)``, ``vp = ⌈n/p⌉``
    (the :class:`PartitionedGraph` rule).  Returns ``(src_rows, local_rows,
    counts)``: two ``(p, cap)`` int32 arrays whose row ``i`` holds partition
    ``i``'s edges by non-decreasing local destination ``dst − i·vp`` (the
    input order kept among equal destinations), then padding (src 0, local
    ``vp``), and the ``(p,)`` int64 counts of real edges; ``cap`` is the
    largest count.  Counts and offsets are int64, so the edge list may hold
    2**31 edges or more; each row stays int32.

    Two stable counting passes on 16-bit digits, on ``workers`` threads
    (numpy's radix argsort, gathers and scatters release the GIL): edge
    chunks by ``(partition, local >> 16)`` straight into their rows, then
    each 65,536-vertex bucket by ``local & 0xffff``.  Host memory beyond the
    output: one uint16 key per edge and one chunk's temporaries per thread.
    """
    src, dst = np.asarray(src), np.asarray(dst)
    m, vp = int(src.shape[0]), max(1, -(-n // p))
    per = -(-vp // (1 << _DIGIT))  # buckets per partition
    n_buckets = p * per  # ≤ 2**16 for any int32 vertex id
    workers = workers or min(32, os.cpu_count() or 1)
    chunk = max(1 << 16, min(1 << 22, -(-m // (4 * workers))))
    starts = list(range(0, m, chunk))

    def local_of(d):
        return d - (d // vp) * vp

    def bucket_of(d):
        owner = d // vp
        return (owner * per + ((d - owner * vp) >> _DIGIT)).astype(np.uint16)

    def keyed(c0):
        k = bucket_of(dst[c0:c0 + chunk])
        return k, np.bincount(k, minlength=n_buckets)

    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        keyed_chunks = list(pool.map(keyed, starts))
        keys = [k for k, _ in keyed_chunks]
        counts = np.zeros((len(starts), n_buckets), np.int64)
        for c, (_, cnt) in enumerate(keyed_chunks):
            counts[c] = cnt
        del keyed_chunks
        per_bucket = counts.sum(axis=0).reshape(p, per)
        rows = per_bucket.sum(axis=1)
        cap = max(1, int(rows.max(initial=0)))
        # where each bucket starts in the flattened (p, cap) output, and
        # where each chunk's run of it starts
        first = (np.arange(p)[:, None] * cap + np.cumsum(per_bucket, axis=1)
                 - per_bucket).reshape(-1)
        chunk_first = first[None, :] + np.cumsum(counts, axis=0) - counts
        src_rows = np.zeros((p, cap), np.int32)
        local_rows = np.full((p, cap), vp, np.int32)
        flat_src, flat_local = src_rows.reshape(-1), local_rows.reshape(-1)

        def scatter(c):
            c0 = starts[c]
            order = np.argsort(keys[c], kind="stable")
            run_first = np.cumsum(counts[c]) - counts[c]
            pos = np.arange(order.size, dtype=np.int64)
            pos += (chunk_first[c] - run_first)[keys[c][order]]
            keys[c] = None
            flat_src[pos] = src[c0:c0 + chunk][order]
            flat_local[pos] = local_of(dst[c0:c0 + chunk][order])

        list(pool.map(scatter, range(len(starts))))

        def order_bucket(b):
            lo = int(first[b])
            hi = lo + int(per_bucket.reshape(-1)[b])
            if hi - lo > 1:
                low = (flat_local[lo:hi] & 0xFFFF).astype(np.uint16)
                order = np.argsort(low, kind="stable")
                flat_src[lo:hi] = flat_src[lo:hi][order]
                flat_local[lo:hi] = flat_local[lo:hi][order]

        list(pool.map(order_bucket, range(n_buckets)))
    return src_rows, local_rows, rows


# ---------------------------------------------------------------------------
# Sequential oracle (numpy, float64)
# ---------------------------------------------------------------------------


def pagerank_numpy(
    g: Graph,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-12,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
    pr0: np.ndarray | None = None,
) -> tuple[np.ndarray, int]:
    """Sequential Jacobi PageRank — the paper's baseline & Lemma-2 reference.

    Doubles as the **weighted float64 oracle**: with ``g.weights`` each edge's
    contribution is scaled per edge, with ``g.bias`` the teleport base is
    scaled per vertex — ``pr = base·bias + d·Σ w·pr(src)/outdeg(src)`` —
    which is the fixed point every registered variant must reproduce on
    weighted graphs (asserted by the tests/test_weighted.py property tier).

    ``pr0`` seeds the iteration (default uniform ``1/n``); the fixed point is
    start-independent, so a warm start — e.g. the previous fixed point after
    a small graph update, via :func:`repro.core.solver.warm_start_pr` — only
    changes the iteration count.
    """
    n = g.n
    inv_out = np.where(g.out_degree > 0, 1.0 / np.maximum(g.out_degree, 1), 0.0)
    base = (1.0 - d) / n
    base_vec = base if g.bias is None else base * g.bias
    pr = (np.full(n, 1.0 / n) if pr0 is None
          else np.asarray(pr0, dtype=np.float64).copy())
    for it in range(1, max_iter + 1):
        contrib = (pr * inv_out)[g.src]
        if g.weights is not None:
            contrib = contrib * g.weights
        acc = np.zeros(n)
        np.add.at(acc, g.dst, contrib)
        new = base_vec + d * acc
        if handle_dangling:
            new = new + d * pr[g.out_degree == 0].sum() / n
        err = np.abs(new - pr).max()
        pr = new
        if err <= threshold:
            return pr, it
    return pr, max_iter


def l1_norm(pr_a, pr_b) -> float:
    """Paper Fig 5/6 metric: sum of per-vertex rank differences."""
    return float(np.abs(np.asarray(pr_a, dtype=np.float64) - np.asarray(pr_b, dtype=np.float64)).sum())


# ---------------------------------------------------------------------------
# Alg 1 — Barrier (Jacobi) and Alg 5 — Barrier-Opt (perforated Jacobi)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("n", "max_iter", "handle_dangling", "perforate")
)
def _barrier_impl(src, dst, inv_out, dangling, weights, bias, warm,
                  *, n, d, threshold, max_iter, handle_dangling, perforate):
    dtype = inv_out.dtype
    base = jnp.asarray((1.0 - d) / n, dtype)
    # weights=None / bias=None are empty pytrees: the branches resolve at
    # trace time, so the unweighted path compiles to exactly the old sweep
    base_vec = base if bias is None else base * bias

    def sweep(pr):
        contrib = (pr * inv_out)[src]
        if weights is not None:
            contrib = contrib * weights
        acc = jax.ops.segment_sum(contrib, dst, num_segments=n, indices_are_sorted=True)
        new = base_vec + d * acc
        if handle_dangling:
            new = new + d * jnp.sum(pr * dangling) / n
        return new

    transforms = (perforation(threshold),) if perforate else ()
    step = barrier_schedule(sweep, transforms)
    # warm=None is an empty pytree: the cold path traces exactly as before
    pr0 = jnp.full((n,), 1.0 / n, dtype) if warm is None else warm
    return solve(step, pr0, threshold=threshold, max_iter=max_iter,
                 track_frozen=perforate)


def _warm_operand(pr0, dtype):
    """Warm-start vector as a jit operand (``None`` stays ``None`` — an
    empty pytree, so cold solves keep their cache entry and trace)."""
    return None if pr0 is None else jnp.asarray(np.asarray(pr0), dtype)


def pagerank_barrier(
    dg: DeviceGraph,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
    pr0=None,
) -> PageRankResult:
    return _barrier_impl(
        dg.src, dg.dst, dg.inv_out, dg.dangling, dg.weights, dg.bias,
        _warm_operand(pr0, dg.inv_out.dtype),
        n=dg.n, d=d, threshold=threshold, max_iter=max_iter,
        handle_dangling=handle_dangling, perforate=False,
    )


def pagerank_barrier_opt(
    dg: DeviceGraph,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
    pr0=None,
) -> PageRankResult:
    return _barrier_impl(
        dg.src, dg.dst, dg.inv_out, dg.dangling, dg.weights, dg.bias,
        _warm_operand(pr0, dg.inv_out.dtype),
        n=dg.n, d=d, threshold=threshold, max_iter=max_iter,
        handle_dangling=handle_dangling, perforate=True,
    )


# ---------------------------------------------------------------------------
# Alg 2 — Barrier-Edge (3-phase, scatter + gather)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n", "m", "max_iter", "handle_dangling"))
def _barrier_edge_impl(src_by_src, edge_slot, dst, inv_out, dangling, weights,
                       bias, warm, *, n, m, d, threshold, max_iter,
                       handle_dangling):
    dtype = inv_out.dtype
    base = jnp.asarray((1.0 - d) / n, dtype)
    base_vec = base if bias is None else base * bias

    def sweep(pr):
        # Phase I: every vertex scatters its contribution into its out-edges'
        # slots of the (dst-ordered) contribution list — paper Alg 2 l.9-12.
        contrib_by_src = (pr * inv_out)[src_by_src]
        contribution_list = jnp.zeros((m,), dtype).at[edge_slot].set(contrib_by_src)
        if weights is not None:  # per-edge weights, applied in dst order
            contribution_list = contribution_list * weights
        # Phase II: gather per destination — paper Alg 2 l.16-23.
        acc = jax.ops.segment_sum(contribution_list, dst, num_segments=n, indices_are_sorted=True)
        new = base_vec + d * acc
        if handle_dangling:
            new = new + d * jnp.sum(pr * dangling) / n
        # Phase III (error fold + swap) is the engine's loop-carried update.
        return new

    step = barrier_schedule(sweep)
    pr0 = jnp.full((n,), 1.0 / n, dtype) if warm is None else warm
    return solve(step, pr0, threshold=threshold, max_iter=max_iter)


def pagerank_barrier_edge(
    eg: EdgeCentricGraph,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
    pr0=None,
) -> PageRankResult:
    return _barrier_edge_impl(
        eg.src_by_src, eg.edge_slot, eg.dst, eg.inv_out, eg.dangling,
        eg.weights, eg.bias, _warm_operand(pr0, eg.inv_out.dtype),
        n=eg.n, m=eg.m, d=d, threshold=threshold, max_iter=max_iter,
        handle_dangling=handle_dangling,
    )


# ---------------------------------------------------------------------------
# Alg 3 — No-Sync (barrier-free; fresh in-iteration reads, single pr array)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("n", "p", "vp", "n_pad", "max_iter", "perforate",
                     "thread_level", "handle_dangling"),
)
def _nosync_impl(
    src_pad, dst_local, emask, inv_out, dangling, bias_pad, warm,
    *, n, p, vp, n_pad, d, threshold, max_iter, perforate, thread_level,
    handle_dangling,
):
    dtype = inv_out.dtype
    base = jnp.asarray((1.0 - d) / n, dtype)

    def sweep(i, pr, dmass):
        # `emask` is the effective per-edge multiplier: the {0,1} validity
        # mask on unweighted graphs, the per-edge weights (0 on padding
        # lanes) on weighted ones — one multiply either way.
        srcs = jax.lax.dynamic_slice_in_dim(src_pad, i, 1, 0)[0]
        dsts = jax.lax.dynamic_slice_in_dim(dst_local, i, 1, 0)[0]
        msk = jax.lax.dynamic_slice_in_dim(emask, i, 1, 0)[0]
        contrib = (pr * inv_out)[srcs] * msk
        acc = jax.ops.segment_sum(contrib, dsts, num_segments=vp, indices_are_sorted=True)
        if bias_pad is None:
            return base + d * acc + dmass
        b_i = jax.lax.dynamic_slice_in_dim(bias_pad, i * vp, vp, 0)
        return base * b_i + d * acc + dmass

    def dangling_mass(pr):
        # snapshot at iteration start (not per partition) — same fixed point
        # (Lemma 2: pr is stationary there), one O(n) reduction per iteration.
        if handle_dangling:
            return d * jnp.sum(pr * dangling) / n
        return jnp.asarray(0.0, dtype)

    transforms = (perforation(threshold),) if perforate else ()
    step = nosync_schedule(
        sweep, p=p, vp=vp, threshold=threshold,
        transforms=transforms, thread_level=thread_level,
        prologue=dangling_mass,
    )
    pr0 = jnp.full((n_pad,), 1.0 / n, dtype) if warm is None else warm
    r = solve(step, pr0, n_units=p, threshold=threshold, max_iter=max_iter,
              track_frozen=perforate)
    return PageRankResult(r.pr[:n], r.iterations, r.err, r.residuals, r.sweeps)


def pagerank_nosync(
    pg: PartitionedGraph,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    perforate: bool = False,
    thread_level: bool = True,
    handle_dangling: bool = False,
    pr0=None,
) -> PageRankResult:
    warm = None
    if pr0 is not None:
        # padding slots start at 0; their first sweep writes base + dmass
        # (they have no in-edges) and they are sliced off on return anyway
        padded = np.zeros(pg.n_pad, dtype=np.float64)
        padded[:pg.n] = np.asarray(pr0)
        warm = jnp.asarray(padded, pg.inv_out.dtype)
    return _nosync_impl(
        pg.src_pad, pg.dst_local, pg.edge_mult, pg.inv_out, pg.dangling,
        pg.bias_pad, warm,
        n=pg.n, p=pg.p, vp=pg.vp, n_pad=pg.n_pad,
        d=d, threshold=threshold, max_iter=max_iter,
        perforate=perforate, thread_level=thread_level,
        handle_dangling=handle_dangling,
    )


# ---------------------------------------------------------------------------
# Residual-adaptive No-Sync (descending-residual order + certified skipping)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("n", "p", "vp", "n_pad", "max_iter", "handle_dangling"),
)
def _nosync_adaptive_impl(
    src_pad, dst_local, emask, inv_out, dangling, bias_pad, gain, warm,
    *, n, p, vp, n_pad, d, threshold, max_iter, handle_dangling,
):
    dtype = inv_out.dtype
    base = jnp.asarray((1.0 - d) / n, dtype)

    def sweep(i, pr, dmass):
        srcs = jax.lax.dynamic_slice_in_dim(src_pad, i, 1, 0)[0]
        dsts = jax.lax.dynamic_slice_in_dim(dst_local, i, 1, 0)[0]
        msk = jax.lax.dynamic_slice_in_dim(emask, i, 1, 0)[0]
        contrib = (pr * inv_out)[srcs] * msk
        acc = jax.ops.segment_sum(contrib, dsts, num_segments=vp, indices_are_sorted=True)
        if bias_pad is None:
            return base + d * acc + dmass
        b_i = jax.lax.dynamic_slice_in_dim(bias_pad, i * vp, vp, 0)
        return base * b_i + d * acc + dmass

    def dangling_mass(pr):
        if handle_dangling:
            return d * jnp.sum(pr * dangling) / n
        return jnp.asarray(0.0, dtype)

    gain_eff = gain
    if handle_dangling:
        # a unit Δ in partition j also moves the redistributed dangling mass
        # by ≤ d·|dangling ∩ j|·Δ/n, uniformly across every vertex
        dang_counts = dangling.reshape(p, vp).sum(axis=1)
        gain_eff = gain + (dang_counts / n)[None, :]

    step = adaptive_schedule(
        sweep, p=p, vp=vp, threshold=threshold, d=d, gain=gain_eff,
        prologue=dangling_mass,
    )
    pr0 = jnp.full((n_pad,), 1.0 / n, dtype) if warm is None else warm
    r = solve(step, pr0, n_units=p, threshold=threshold, max_iter=max_iter,
              aux0=jnp.full((n_pad,), jnp.inf, dtype))
    return PageRankResult(r.pr[:n], r.iterations, r.err, r.residuals, r.sweeps)


def pagerank_nosync_adaptive(
    pg: PartitionedGraph,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
    pr0=None,
) -> PageRankResult:
    """Alg-3 partition sweeps on the residual-adaptive schedule: partitions
    swept in descending residual-bound order, partitions whose certified
    per-vertex bound sits at or below the fair-share cut skipped outright
    (see :func:`repro.core.solver.adaptive_schedule`).  Same fixed point as
    ``nosync``; strictly less work on graphs whose partitions converge at
    uneven rates — the regression tier in tests/test_adaptive.py asserts the
    sweep-count win."""
    if pg.gain is None:
        raise ValueError(
            "PartitionedGraph bundle lacks the gain matrix required by the "
            "adaptive schedule (rebuild with PartitionedGraph.from_graph)")
    warm = None
    if pr0 is not None:
        padded = np.zeros(pg.n_pad, dtype=np.float64)
        padded[:pg.n] = np.asarray(pr0)
        warm = jnp.asarray(padded, pg.inv_out.dtype)
    return _nosync_adaptive_impl(
        pg.src_pad, pg.dst_local, pg.edge_mult, pg.inv_out, pg.dangling,
        pg.bias_pad, pg.gain, warm,
        n=pg.n, p=pg.p, vp=pg.vp, n_pad=pg.n_pad,
        d=d, threshold=threshold, max_iter=max_iter,
        handle_dangling=handle_dangling,
    )


# ---------------------------------------------------------------------------
# STIC-D identical-node variants
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class IdenticalNodePlan:
    """Preprocessing for the *-Identical variants.

    ``rep_of[u]``: representative vertex of u's identical-in-neighbour class.
    Only edges whose dst is a representative are kept; after each sweep ranks
    are broadcast from representatives to their class members.  On weighted/
    biased graphs the class key covers weights and bias too (see
    :meth:`repro.graphs.csr.Graph.in_neighbor_classes`), so sharing stays
    exact: the representative's weighted in-edges and bias ARE the class's.
    """

    n: int
    n_classes: int
    cls_of: jax.Array  # (n,) int32 — class id per vertex
    src: jax.Array  # edges into representatives, dst-sorted
    dst_class: jax.Array  # class id per kept edge
    inv_out: jax.Array
    dangling: jax.Array
    weights: jax.Array | None = None  # kept-edge weights
    bias: jax.Array | None = None  # (n,) base multiplier

    @classmethod
    def from_graph(cls, g: Graph, dtype=jnp.float32) -> "IdenticalNodePlan":
        cls_of = g.in_neighbor_classes()
        n_classes = int(cls_of.max()) + 1 if g.n else 0
        rep = np.full(n_classes, -1, dtype=np.int64)
        for u in range(g.n):
            if rep[cls_of[u]] < 0:
                rep[cls_of[u]] = u
        keep = rep[cls_of[g.dst]] == g.dst  # only edges into representatives
        inv, dang = inv_out_and_dangling(g.out_degree)
        return cls(
            n=g.n,
            n_classes=n_classes,
            cls_of=jnp.asarray(cls_of.astype(np.int32)),
            src=jnp.asarray(g.src[keep]),
            dst_class=jnp.asarray(cls_of[g.dst[keep]].astype(np.int32)),
            inv_out=jnp.asarray(inv, dtype=dtype),
            dangling=jnp.asarray(dang, dtype=dtype),
            weights=(None if g.weights is None
                     else jnp.asarray(g.weights[keep], dtype=dtype)),
            bias=None if g.bias is None else jnp.asarray(g.bias, dtype=dtype),
        )


@functools.partial(
    jax.jit, static_argnames=("n", "n_classes", "max_iter", "handle_dangling")
)
def _identical_impl(cls_of, src, dst_class, inv_out, dangling, weights, bias,
                    warm, *, n, n_classes, d, threshold, max_iter,
                    handle_dangling):
    dtype = inv_out.dtype
    base = jnp.asarray((1.0 - d) / n, dtype)
    base_vec = base if bias is None else base * bias

    def sweep(pr):
        contrib = (pr * inv_out)[src]
        if weights is not None:
            contrib = contrib * weights
        acc_cls = jax.ops.segment_sum(contrib, dst_class, num_segments=n_classes)
        new = base_vec + d * acc_cls[cls_of]  # one computation per class, broadcast
        if handle_dangling:
            # dangling mass is uniform across vertices, so identical-in-
            # neighbour classes stay identical under redistribution.
            new = new + d * jnp.sum(pr * dangling) / n
        return new

    step = barrier_schedule(sweep)
    pr0 = jnp.full((n,), 1.0 / n, dtype) if warm is None else warm
    return solve(step, pr0, threshold=threshold, max_iter=max_iter)


def pagerank_identical(
    plan: IdenticalNodePlan,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
    pr0=None,
) -> PageRankResult:
    return _identical_impl(
        plan.cls_of, plan.src, plan.dst_class, plan.inv_out, plan.dangling,
        plan.weights, plan.bias, _warm_operand(pr0, plan.inv_out.dtype),
        n=plan.n, n_classes=plan.n_classes, d=d, threshold=threshold,
        max_iter=max_iter, handle_dangling=handle_dangling,
    )


# ---------------------------------------------------------------------------
# Registry entries — the declarative form of the variants above
# ---------------------------------------------------------------------------


def _run_kw(kw: dict) -> dict:
    """Solver kwargs every run fn understands (drops build-only opts).
    ``pr0`` (the warm-start transport option) rides along when given."""
    return {k: kw[k] for k in ("d", "threshold", "max_iter", "handle_dangling",
                               "pr0")
            if k in kw}


def _sequential_run(g, **kw):
    pr, it = pagerank_numpy(g, **_run_kw(kw))
    return PageRankResult(pr, it, np.asarray(0.0))


register_variant(
    "sequential", build=lambda g, **_: g, run=_sequential_run,
    description="numpy float64 Jacobi oracle (paper baseline)",
    layout="host", backend="numpy", schedule="sequential",
)
register_variant(
    "barrier",
    build=lambda g, **_: DeviceGraph.from_graph(g),
    run=lambda b, **kw: pagerank_barrier(b, **_run_kw(kw)),
    description="Alg 1: Jacobi power iteration (vertex-centric)",
    layout="device", backend="jax", schedule="barrier",
)
register_variant(
    "barrier_edge",
    build=lambda g, **_: EdgeCentricGraph.from_graph(g),
    run=lambda b, **kw: pagerank_barrier_edge(b, **_run_kw(kw)),
    description="Alg 2: 3-phase edge-centric scatter/gather",
    layout="edge", backend="jax", schedule="barrier",
)
register_variant(
    "barrier_opt",
    build=lambda g, **_: DeviceGraph.from_graph(g),
    run=lambda b, **kw: pagerank_barrier_opt(b, **_run_kw(kw)),
    description="Alg 1 + Alg 5 loop perforation",
    layout="device", backend="jax", schedule="barrier",
)
register_variant(
    "barrier_identical",
    build=lambda g, **_: IdenticalNodePlan.from_graph(g),
    run=lambda b, **kw: pagerank_identical(b, **_run_kw(kw)),
    description="STIC-D identical-node sharing on the barrier schedule",
    layout="identical", backend="jax", schedule="barrier",
)
register_variant(
    "nosync",
    build=lambda g, threads=56, **_: PartitionedGraph.from_graph(g, p=threads),
    run=lambda b, thread_level=True, **kw: pagerank_nosync(
        b, thread_level=thread_level, **_run_kw(kw)),
    description="Alg 3: barrier-free fresh-read partition sweeps",
    options=("thread_level",),
    layout="partitioned", backend="jax", schedule="nosync",
)
register_variant(
    "nosync_adaptive",
    build=lambda g, threads=56, **_: PartitionedGraph.from_graph(g, p=threads),
    run=lambda b, **kw: pagerank_nosync_adaptive(b, **_run_kw(kw)),
    description="Alg 3 + residual-adaptive order and certified partition skipping",
    layout="partitioned", backend="jax", schedule="adaptive",
)
register_variant(
    "nosync_opt",
    build=lambda g, threads=56, **_: PartitionedGraph.from_graph(g, p=threads),
    run=lambda b, thread_level=True, **kw: pagerank_nosync(
        b, perforate=True, thread_level=thread_level, **_run_kw(kw)),
    description="Alg 3 + Alg 5 loop perforation",
    options=("thread_level",),
    layout="partitioned", backend="jax", schedule="nosync",
)
# STIC-D decomposition as a plan stage (identical+chain+dead pruned at build,
# mid-graph chains contracted into weighted core edges + bias folds,
# reconstructed after the core converges).  The plan composes with ANY inner
# build — plan first, partition/block the core second — these two entries are
# the paper's Alg-4 completion on both schedules.
register_variant(
    "barrier_sticd",
    build=plan_build("barrier"),
    run=plan_run,
    description="STIC-D plan (identical+chain+dead pruned, chains contracted) + Alg-1 core solve",
    layout="sticd_device", backend="jax", schedule="barrier",
)
register_variant(
    "nosync_sticd",
    build=plan_build("nosync"),
    run=plan_run,
    description="STIC-D plan + Alg-3 no-sync core solve (weighted core partitioned)",
    options=("thread_level",),
    layout="sticd_partitioned", backend="jax", schedule="nosync",
)
