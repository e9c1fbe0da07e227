"""Graph containers: CSR (host) and TPU-friendly blocked COO.

The paper (§4) stores graphs in CSR and iterates either vertex-centric
(in-links per vertex) or edge-centric (explicit contribution list).  On TPU
the hot path is a gather + segment-sum over edges sorted by destination; the
Pallas kernel additionally wants a 2-D *blocked* layout (propagation blocking,
paper ref [17]) so that the rank slice addressed by one tile fits in VMEM.

Graphs are optionally **weighted and biased** (see :class:`Graph.weights` /
:class:`Graph.bias`): the generalized sweep every solver applies is

    pr(v) = base·bias(v) + d · Σ_{(u,v)∈E} w(u,v) · pr(u) / outdeg(u)

with ``base = (1-d)/n``.  ``weights=None`` / ``bias=None`` mean all-ones and
every solver keeps its unweighted fast path in that case.  The weighted form
is what lets :class:`DecompositionPlan` contract chains *in the middle* of
the graph: a pruned chain ``u→c₁→…→c_k→v`` becomes one core edge ``u→v``
with weight ``d^k`` plus a fold of the chain's teleport contribution
``d+d²+…+d^k`` into ``v``'s bias (see docs/DECOMPOSITION.md for the worked
derivation).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import numpy as np

# Matches repro.core.solver.DEFAULT_DAMPING (not imported: csr is the
# dependency-free base layer).  Contracted-edge weights are powers of the
# damping factor, so the decomposition must bake a concrete d at plan time;
# solver.plan_run re-plans when the run-time d differs.
_DEFAULT_DAMPING = 0.85


def _update_pairs(pairs, name: str, n: int) -> np.ndarray:
    """Validate one :meth:`Graph.apply_updates` operand into ``(k, 2)`` int64
    ``(src, dst)`` rows; ``None``/empty become a zero-row array."""
    if pairs is None:
        return np.zeros((0, 2), dtype=np.int64)
    arr = np.asarray(pairs, dtype=np.int64)
    if arr.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    if arr.ndim != 2 or arr.shape[1] != 2:
        raise ValueError(f"{name} must be a (k, 2) array of (src, dst) pairs")
    if arr.min() < 0 or arr.max() >= n:
        raise ValueError(f"{name} endpoint out of range [0, {n})")
    return arr


@dataclasses.dataclass
class GraphDelta:
    """Record of one :meth:`Graph.apply_updates` batch.

    Everything an incremental consumer needs to localize its repair work:
    the applied edge lists (in the canonical dst-major order they were merged
    in), the vertices whose out-/in-edge sets changed, and the dangling-status
    transitions (a vertex losing its last out-edge changes the walk matrix's
    column to zero — the delta-push corrector and the warm-start renormalizer
    both key off these).  ``touched_dst_blocks`` names the dst blocks of a
    :class:`BlockedCOO` layout whose tiles :func:`patch_blocked_coo` must
    rebuild — and, symmetrically, the blocks a serving cache must invalidate.
    """

    n: int
    added: np.ndarray  # (ka, 2) int64 (src, dst), dst-major applied order
    deleted: np.ndarray  # (kd, 2) int64, dst-major applied order
    added_weights: Optional[np.ndarray]  # (ka,) float64; None when unweighted
    touched_src: np.ndarray  # unique vertices whose out-edge set changed
    touched_dst: np.ndarray  # unique vertices whose in-edge set changed
    newly_dangling: np.ndarray  # out-degree dropped >0 -> 0
    undangled: np.ndarray  # out-degree rose 0 -> >0

    @property
    def num_ops(self) -> int:
        return int(self.added.shape[0] + self.deleted.shape[0])

    def touched_vertices(self) -> np.ndarray:
        """Unique vertices appearing as either endpoint of any update."""
        return np.unique(np.r_[self.touched_src, self.touched_dst])

    def touched_dst_blocks(self, block: int) -> np.ndarray:
        """Sorted unique dst blocks (width ``block``) the updates landed in."""
        if self.touched_dst.size == 0:
            return np.zeros(0, dtype=np.int64)
        return np.unique(self.touched_dst // block)


def _concat_ranges(ptr: np.ndarray, verts: np.ndarray) -> np.ndarray:
    """Concatenated CSR index ranges ``ptr[v]:ptr[v+1]`` for each v in verts.

    The decomposition analyses propagate frontiers with this so each wave
    touches only the edges incident to the previous wave — O(n+m) total
    instead of one full edge scan per wave (quadratic on deep chains)."""
    starts = ptr[verts]
    lens = (ptr[verts + 1] - starts).astype(np.int64)
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    off = np.repeat(starts - np.r_[0, np.cumsum(lens)[:-1]], lens)
    return off + np.arange(total, dtype=np.int64)


@dataclasses.dataclass
class Graph:
    """Host-side immutable graph in dst-sorted COO + CSR-by-destination.

    ``src``/``dst`` are parallel edge arrays sorted by ``dst`` (then ``src``):
    this is exactly the order a CSR-of-in-links traversal visits edges, so the
    vertex-centric paper algorithms map onto contiguous edge ranges.

    ``weights`` (per-edge, aligned with the dst-sorted edge arrays) scales
    each edge's ``pr(src)/outdeg(src)`` contribution; ``bias`` (per-vertex)
    multiplies the ``(1-d)/n`` teleport base.  Both default to ``None``
    (all-ones): every solver detects ``None`` and keeps its unweighted fast
    path.  Weights are expected in ``(0, 1]`` — the decomposition only emits
    powers of ``d`` — which also keeps the push solver's L1 certificate
    valid (substochastic walk matrix).
    """

    n: int
    src: np.ndarray  # (m,) int32, sorted by dst
    dst: np.ndarray  # (m,) int32, non-decreasing
    out_degree: np.ndarray  # (n,) int32
    in_ptr: np.ndarray  # (n+1,) int64 CSR indptr over dst
    weights: Optional[np.ndarray] = None  # (m,) float64, dst-sorted; None = 1s
    bias: Optional[np.ndarray] = None  # (n,) float64 base multiplier; None = 1s

    # CSR by source (out-links) — needed by the edge-centric variants, built lazily.
    _out_ptr: Optional[np.ndarray] = None
    _out_dst: Optional[np.ndarray] = None
    _out_edge_slot: Optional[np.ndarray] = None  # position in dst-sorted order

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    @property
    def is_memmap(self) -> bool:
        """True when the edge arrays are ``np.memmap``-backed (store-loaded).

        Every analysis and downstream build works off the array protocol —
        slicing/fancy-indexing a memmap materializes only the touched range —
        so this is informational (benchmarks record it), not a capability
        switch."""
        return isinstance(self.src, np.memmap)

    @classmethod
    def from_arrays(cls, n: int, src: np.ndarray, dst: np.ndarray,
                    out_degree: np.ndarray, in_ptr: np.ndarray,
                    weights: Optional[np.ndarray] = None,
                    bias: Optional[np.ndarray] = None) -> "Graph":
        """Trusted constructor over pre-derived arrays — no sort, no copy.

        This is the store loader's entry (:mod:`repro.graphs.store`): the
        on-disk format already holds dst-sorted edges plus the derived
        ``out_degree``/``in_ptr``, and the arrays may be read-only
        ``np.memmap`` views.  Callers must guarantee the :class:`Graph`
        invariants (dst-sorted order, consistent degrees/indptr) —
        :meth:`repro.graphs.store.GraphStore.graph` does, validated at
        store-write time."""
        return cls(n=n, src=src, dst=dst, out_degree=out_degree,
                   in_ptr=in_ptr, weights=weights, bias=bias)

    def edge_chunks(
        self, chunk_edges: int = 1 << 20,
    ) -> Iterator[tuple[int, np.ndarray, np.ndarray, Optional[np.ndarray]]]:
        """Yield ``(lo, src, dst, weights)`` chunks of the dst-sorted edge
        arrays as **resident** ndarrays (``weights`` is ``None`` on
        unweighted graphs).

        The streaming accessor every out-of-core consumer iterates —
        store writers, the reorder rewrite, blocked-layout statistics —
        so peak memory stays O(chunk_edges) even when the graph itself is
        a memmap view of a much larger store."""
        if chunk_edges < 1:
            raise ValueError("chunk_edges must be >= 1")
        for lo in range(0, self.m, chunk_edges):
            hi = min(lo + chunk_edges, self.m)
            w = None if self.weights is None else np.asarray(self.weights[lo:hi])
            yield lo, np.asarray(self.src[lo:hi]), np.asarray(self.dst[lo:hi]), w

    def materialize(self) -> "Graph":
        """Copy of this graph with every array resident in RAM.

        Device builds ultimately materialize whatever they touch anyway;
        this is for callers that iterate many passes over a memmap-backed
        graph (e.g. the in-RAM oracle during store verification) and would
        otherwise re-page the file each pass."""
        return Graph(
            n=self.n,
            src=np.asarray(self.src).copy(),
            dst=np.asarray(self.dst).copy(),
            out_degree=np.asarray(self.out_degree).copy(),
            in_ptr=np.asarray(self.in_ptr).copy(),
            weights=(None if self.weights is None
                     else np.asarray(self.weights).copy()),
            bias=None if self.bias is None else np.asarray(self.bias).copy(),
        )

    @classmethod
    def from_edges(cls, n: int, src: np.ndarray, dst: np.ndarray,
                   weights: Optional[np.ndarray] = None,
                   bias: Optional[np.ndarray] = None) -> "Graph":
        src = np.asarray(src, dtype=np.int32)
        dst = np.asarray(dst, dtype=np.int32)
        if src.shape != dst.shape:
            raise ValueError("src/dst must be parallel arrays")
        if src.size and (src.min() < 0 or src.max() >= n or dst.min() < 0 or dst.max() >= n):
            raise ValueError("edge endpoint out of range")
        order = np.lexsort((src, dst))
        src, dst = src[order], dst[order]
        if weights is not None:
            weights = np.asarray(weights, dtype=np.float64)
            if weights.shape != src.shape:
                raise ValueError("weights must parallel src/dst")
            weights = weights[order]
        if bias is not None:
            bias = np.asarray(bias, dtype=np.float64)
            if bias.shape != (n,):
                raise ValueError(f"bias must have shape ({n},)")
        out_degree = np.bincount(src, minlength=n).astype(np.int32)
        in_ptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.bincount(dst, minlength=n), out=in_ptr[1:])
        return cls(n=n, src=src, dst=dst, out_degree=out_degree, in_ptr=in_ptr,
                   weights=weights, bias=bias)

    def out_csr(self):
        """CSR over out-links: (out_ptr, out_dst, edge_slot).

        ``edge_slot[j]`` gives, for the j-th edge in src-sorted order, its
        index in the canonical dst-sorted order — this is the paper's
        ``offsetList`` (Alg 2 line 11): where a vertex writes its contribution
        so that the destination's in-link scan finds it contiguously.
        """
        if self._out_ptr is None:
            order = np.lexsort((self.dst, self.src))
            self._out_dst = self.dst[order]
            self._out_edge_slot = order.astype(np.int64)
            out_ptr = np.zeros(self.n + 1, dtype=np.int64)
            np.cumsum(np.bincount(self.src, minlength=self.n), out=out_ptr[1:])
            self._out_ptr = out_ptr
        return self._out_ptr, self._out_dst, self._out_edge_slot

    def in_neighbor_classes(self) -> np.ndarray:
        """STIC-D 'identical nodes': class id per vertex; vertices with the
        same in-neighbor set share a class (identical PageRank).

        On weighted/biased graphs the class key also covers the in-edge
        weights and the vertex's bias — two vertices share a rank only when
        their whole update rule matches, not just the neighbour set."""
        keys = {}
        cls_of = np.empty(self.n, dtype=np.int64)
        for u in range(self.n):
            lo, hi = self.in_ptr[u], self.in_ptr[u + 1]
            key = self.src[lo:hi].tobytes()
            if self.weights is not None:
                key = (key, self.weights[lo:hi].tobytes())
            if self.bias is not None:
                key = (key, float(self.bias[u]))
            cls_of[u] = keys.setdefault(key, len(keys))
        return cls_of

    def chain_nodes(self) -> np.ndarray:
        """STIC-D 'chain nodes': (n,) bool mask of in-degree-1/out-degree-1
        path vertices whose rank is a closed form of the chain head's rank.

        A vertex ``v`` with a single in-neighbour ``u`` satisfies
        ``pr(v) = (1-d)/n + d * pr(u) / outdeg(u)`` exactly, so a run of
        indeg-1/outdeg-1 vertices is an affine (geometric) function of the
        first non-chain ancestor — the *head*.  Members of pure indeg-1/
        outdeg-1 cycles have no head (the walk never leaves the cycle) and
        are excluded: their ranks are genuinely iterative.
        """
        indeg = np.diff(self.in_ptr)
        cand = (indeg == 1) & (self.out_degree == 1)
        ok = np.zeros(self.n, dtype=bool)
        if not cand.any():
            return ok
        cidx = np.flatnonzero(cand)
        pred = self.src[self.in_ptr[:-1][cidx]]  # the single in-edge
        # propagate headed-ness down the chains, frontier by frontier (a
        # candidate successor's only predecessor IS the frontier vertex, so
        # it becomes headed); cycle members never acquire it
        ok[cidx] = ~cand[pred]
        out_ptr, out_dst, _ = self.out_csr()
        frontier = np.flatnonzero(ok)
        while frontier.size:
            succ = out_dst[_concat_ranges(out_ptr, frontier)]
            newly = np.unique(succ[cand[succ] & ~ok[succ]])
            ok[newly] = True
            frontier = newly
        return ok

    def source_chain_nodes(self) -> np.ndarray:
        """STIC-D extension, 'source chains': (n,) bool mask of indeg-0/
        outdeg-1 vertices.

        Such a vertex has no in-edges, so its rank is the closed form
        ``pr(s) = base·bias(s)`` exactly — no head needed.  It starts a chain
        run (its outdeg-1 successors with indeg 1 are ordinary
        :meth:`chain_nodes` members, headed by ``s``), and the whole run's
        contribution to its terminal vertex is a pure bias fold: unlike a
        headed chain there is no ``pr(head)`` term to carry, so pruning needs
        no weighted edge at all.  Only meaningful to a plan that can fold
        biases (:class:`DecompositionPlan` with ``contract=True``)."""
        indeg = np.diff(self.in_ptr)
        return (indeg == 0) & (self.out_degree == 1)

    def dead_nodes(self) -> np.ndarray:
        """STIC-D 'dead nodes': (n,) bool mask of vertices from which every
        forward path ends in a sink — the least fixed point of "out-degree 0,
        or all out-neighbours dead".

        Dead vertices influence no live vertex's rank (their mass never flows
        back), so they can be pruned from the iteration and their ranks
        back-propagated in one topological pass after the core converges.
        Cycles are never marked (a cycle member always has a live successor),
        so the dead set induces a DAG and the back-propagation is well-defined.
        """
        dead = self.out_degree == 0
        frontier = np.flatnonzero(dead)
        if frontier.size == 0:
            return dead
        # Kahn-style peel: live_out[u] counts u's edges to live vertices;
        # each death decrements its in-neighbours, so every edge is touched
        # once overall.
        live_out = self.out_degree.astype(np.int64)
        while frontier.size:
            srcs = self.src[_concat_ranges(self.in_ptr, frontier)]
            np.subtract.at(live_out, srcs, 1)
            touched = np.unique(srcs)
            newly = touched[(live_out[touched] == 0) & ~dead[touched]]
            dead[newly] = True
            frontier = newly
        return dead

    def partition_ranges(self, p: int, edge_balanced: bool = True) -> np.ndarray:
        """(p+1,) vertex boundaries. Paper uses static equal-vertex partitions;
        we default to edge-balanced boundaries (fixes their load-skew issue).

        ``edge_balanced=False`` reproduces the ``ceil(n/p)`` splits
        :meth:`PartitionedGraph.from_graph` actually allocates (trailing
        partitions may be empty), so per-partition costs derived from these
        boundaries describe the runtime layout exactly."""
        if not edge_balanced:
            vp = -(-self.n // p) if self.n else 0
            return np.minimum(np.arange(p + 1, dtype=np.int64) * vp, self.n)
        targets = np.linspace(0, self.m, p + 1)
        bounds = np.searchsorted(self.in_ptr, targets, side="left")
        bounds[0], bounds[-1] = 0, self.n
        return np.maximum.accumulate(bounds).astype(np.int64)

    def apply_updates(
        self,
        adds=None,
        dels=None,
        add_weights: Optional[np.ndarray] = None,
    ) -> tuple["Graph", "GraphDelta"]:
        """Apply an edge-update batch and return ``(new_graph, delta)``.

        ``adds``/``dels`` are ``(k, 2)`` arrays of ``(src, dst)`` pairs over
        the *existing* vertex set (``n`` never changes — vertex-set growth is
        a rebuild, edge churn is not).  The derived state is re-derived
        **incrementally**, never from scratch: the dst-sorted edge arrays are
        patched by one O(m+k) merge (delete positions located by binary
        search, insert positions by binary search into the survivors),
        ``out_degree`` and ``in_ptr`` are adjusted by per-endpoint deltas, and
        ``bias`` is carried through untouched.  ``self`` is left unmodified
        (untouched arrays may be shared with the result, so treat graphs as
        immutable as ever); memmap-backed graphs work — touched ranges are
        materialized, the rest stays on disk.

        Semantics, enforced rather than guessed:

        * deletions are applied first, then additions — so a batch may delete
          an edge and re-add it (a weight update, on weighted graphs);
        * deleting an edge that does not exist **raises** (``ValueError``),
          as does deleting the same edge twice in one batch — a silent no-op
          would desynchronize every incremental consumer downstream;
        * adding an edge twice in one batch raises; adding an edge that
          already exists (and survives the batch's deletions) raises on
          unweighted graphs — unweighted parallel edges would silently
          double-count.  Weighted graphs permit parallel edges (the STIC-D
          contraction emits them legitimately); deletion then removes the
          first of the parallel copies in canonical order;
        * ``add_weights`` (per added edge, default all-ones) is only accepted
          on weighted graphs.

        The returned :class:`GraphDelta` records exactly what changed —
        including vertices that became dangling (last out-edge deleted) or
        stopped being dangling — so repair passes, layout patching
        (:func:`patch_blocked_coo`), and plan invalidation
        (:meth:`DecompositionPlan.touched_by`) can all localize their work.
        """
        n = self.n
        adds_a = _update_pairs(adds, "adds", n)
        dels_a = _update_pairs(dels, "dels", n)
        if add_weights is not None:
            if self.weights is None:
                raise ValueError(
                    "add_weights given but the graph is unweighted")
            add_w = np.asarray(add_weights, dtype=np.float64)
            if add_w.shape != (adds_a.shape[0],):
                raise ValueError(
                    f"add_weights must have shape ({adds_a.shape[0]},), "
                    f"got {add_w.shape}")
        elif self.weights is not None:
            add_w = np.ones(adds_a.shape[0], dtype=np.float64)
        else:
            add_w = None

        src = np.asarray(self.src)
        dst = np.asarray(self.dst)
        m = int(src.shape[0])
        # dst-major edge key: ascending in the canonical (dst, then src) sort
        key = dst.astype(np.int64) * n + src

        # --- deletions: locate each edge by binary search, verify, mask ---
        del_order = np.argsort(dels_a[:, 1] * n + dels_a[:, 0], kind="stable")
        dels_s = dels_a[del_order]
        dk = dels_s[:, 1] * n + dels_s[:, 0]
        if dk.size and np.any(dk[1:] == dk[:-1]):
            i = int(np.flatnonzero(dk[1:] == dk[:-1])[0])
            raise ValueError(
                f"duplicate delete of edge ({int(dels_s[i, 0])} -> "
                f"{int(dels_s[i, 1])}) in one batch")
        keep = np.ones(m, dtype=bool)
        if dk.size:
            if m == 0:
                raise ValueError(
                    f"cannot delete nonexistent edge ({int(dels_s[0, 0])} -> "
                    f"{int(dels_s[0, 1])})")
            pos = np.searchsorted(key, dk)
            ok = (pos < m) & (key[np.minimum(pos, m - 1)] == dk)
            if not np.all(ok):
                i = int(np.flatnonzero(~ok)[0])
                raise ValueError(
                    f"cannot delete nonexistent edge ({int(dels_s[i, 0])} -> "
                    f"{int(dels_s[i, 1])})")
            keep[pos] = False

        # --- additions: dedupe-check, then one sorted merge-insert ---
        add_order = np.argsort(adds_a[:, 1] * n + adds_a[:, 0], kind="stable")
        adds_s = adds_a[add_order]
        ak = adds_s[:, 1] * n + adds_s[:, 0]
        if ak.size and np.any(ak[1:] == ak[:-1]):
            i = int(np.flatnonzero(ak[1:] == ak[:-1])[0])
            raise ValueError(
                f"duplicate add of edge ({int(adds_s[i, 0])} -> "
                f"{int(adds_s[i, 1])}) in one batch")
        key_kept = key[keep]
        if ak.size and self.weights is None and key_kept.size:
            p = np.searchsorted(key_kept, ak)
            exists = (p < key_kept.size) \
                & (key_kept[np.minimum(p, key_kept.size - 1)] == ak)
            if np.any(exists):
                i = int(np.flatnonzero(exists)[0])
                raise ValueError(
                    f"duplicate add: edge ({int(adds_s[i, 0])} -> "
                    f"{int(adds_s[i, 1])}) already present (unweighted "
                    f"graphs reject parallel edges)")
        ins = np.searchsorted(key_kept, ak)
        new_src = np.insert(src[keep], ins, adds_s[:, 0].astype(src.dtype))
        new_dst = np.insert(dst[keep], ins, adds_s[:, 1].astype(dst.dtype))
        new_w = None
        if self.weights is not None:
            w = np.asarray(self.weights)
            new_w = np.insert(w[keep], ins, add_w[add_order])

        # --- derived state: per-endpoint count deltas, not a recount ---
        old_out = np.asarray(self.out_degree)
        new_out = old_out.astype(np.int32, copy=True)
        np.subtract.at(new_out, dels_a[:, 0], 1)
        np.add.at(new_out, adds_a[:, 0], 1)
        in_counts = np.diff(np.asarray(self.in_ptr))
        np.subtract.at(in_counts, dels_a[:, 1], 1)
        np.add.at(in_counts, adds_a[:, 1], 1)
        in_ptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(in_counts, out=in_ptr[1:])

        touched_src = np.unique(np.r_[adds_a[:, 0], dels_a[:, 0]])
        touched_dst = np.unique(np.r_[adds_a[:, 1], dels_a[:, 1]])
        delta = GraphDelta(
            n=n,
            added=adds_s,
            deleted=dels_s,
            added_weights=None if add_w is None else add_w[add_order],
            touched_src=touched_src,
            touched_dst=touched_dst,
            newly_dangling=touched_src[(old_out[touched_src] > 0)
                                       & (new_out[touched_src] == 0)],
            undangled=touched_src[(old_out[touched_src] == 0)
                                  & (new_out[touched_src] > 0)],
        )
        g_new = Graph(n=n, src=new_src, dst=new_dst, out_degree=new_out,
                      in_ptr=in_ptr, weights=new_w, bias=self.bias)
        return g_new, delta


@dataclasses.dataclass(frozen=True)
class EdgeList:
    """An unsorted edge list as a loader hands it over: ``n`` vertices and
    the directed edges ``src[k] → dst[k]`` (int32 endpoints, repeats and
    self-loops allowed), with nothing derived from them.  The sharded build
    (:meth:`repro.core.distributed.DistributedBundle.build`) takes it where a
    :class:`Graph`, whose constructor sorts every edge, would not fit."""

    n: int
    src: np.ndarray
    dst: np.ndarray

    @property
    def m(self) -> int:
        return int(self.src.shape[0])


def inv_out_and_dangling(out_degree: np.ndarray, n_pad: Optional[int] = None):
    """``(inv_out, dangling)`` float64 host arrays shared by every device
    bundle: 1/outdeg (0 for dangling vertices) and the outdeg==0 mask.
    With ``n_pad`` both are zero-padded — padding slots are neither sources
    nor dangling."""
    n = out_degree.shape[0]
    size = n if n_pad is None else n_pad
    out = np.zeros(size, dtype=np.float64)
    out[:n] = out_degree
    inv = np.where(out > 0, 1.0 / np.maximum(out, 1), 0.0)
    dang = np.zeros(size, dtype=np.float64)
    dang[:n] = out_degree == 0
    return inv, dang


# ---------------------------------------------------------------------------
# STIC-D build-time decomposition: shrink the graph to its iterative core
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DecompositionPlan:
    """Build-time STIC-D decomposition: prune identical/chain/dead vertices
    out of the iteration, solve the shrunken *core*, reconstruct afterwards.

    The core is an ordinary :class:`Graph` (with the **full-graph**
    out-degrees retained, so 1/outdeg contributions are unchanged), which is
    what makes the plan composable with every registered variant: plan first,
    then hand ``plan.core`` to any ``build`` — partitioned, blocked-Pallas,
    distributed — and the solve runs on the smaller problem unchanged.

    Four vertex classes are removed, all exactly (same fixed point):

    * **identical** — non-representative members of an identical-in-neighbour
      class (:meth:`Graph.in_neighbor_classes`) whose out-degree matches the
      representative's.  Their rank equals the representative's, so their
      out-edges are *rewired* to the representative (same ``pr(src)/outdeg``
      contribution) and the member drops out of the core entirely.
    * **chain** — indeg-1/outdeg-1 paths (:meth:`Graph.chain_nodes`): rank is
      a closed form of the head, restored by the reconstruction pass.
    * **source chain** — indeg-0/outdeg-1 starters
      (:meth:`Graph.source_chain_nodes`): rank is the closed form
      ``base·bias`` with no head at all.
    * **dead** — the sink closure (:meth:`Graph.dead_nodes`): rank is
      back-propagated in topological waves once the core has converged.

    With ``contract=True`` (the default) *every* headed chain is pruned, not
    just the suffixes that drain into the dead region: a chain
    ``u→c₁→…→c_k→v`` that re-enters the core at ``v`` is collapsed into one
    **weighted** core edge ``u→v`` carrying the walk probability of the whole
    path (``d^k`` for unit-weight edges) while the chain's accumulated
    teleport contribution (``d+d²+…+d^k`` times the base) is folded into
    ``v``'s **bias** multiplier.  Source-chain runs fold the same bias term
    but emit no edge (there is no head whose rank could flow).  Both folds
    depend on the damping factor, so the plan bakes ``d`` at build time
    (:attr:`d`); ``repro.core.solver.plan_run`` re-plans when the run-time
    ``d`` differs.  ``contract=False`` reproduces the PR-3 suffix-only
    closure (kept for comparison benchmarks/tests).

    Dangling redistribution composes in closed form: the redistributed fixed
    point is a scalar multiple ``c·pr`` of the plain one, with
    ``c = base/(base − (d/n)·Σ_dangling pr)`` (substitute ``c·pr`` into the
    redistributed equation to see the relation; on unweighted graphs this is
    exactly L1 normalisation, and it stays exact when per-edge weights < 1
    leak mass).  So the core always solves with ``handle_dangling=False``
    and :meth:`reconstruct` rescales at the end.  The argument needs the
    full graph's teleport to be *uniform* — the core's chain-folded bias is
    fine (both fixed points scale the same bias vector), but an explicitly
    biased input graph is rejected under ``handle_dangling``.  Likewise the
    core solve's ``(1-d)/n_core`` base is rescaled by linearity: the
    full-graph restriction is ``core_pr · n_core / n``.
    """

    n: int
    core: Graph  # shrunken graph; out_degree holds FULL-graph degrees
    core_index: np.ndarray  # (n_core,) full-graph ids of core vertices
    full_to_core: np.ndarray  # (n,) core slot per vertex, -1 if pruned
    struct_pruned: np.ndarray  # (n,) bool — chain/source-chain/dead prune set
    chain_mask: np.ndarray  # (n,) bool — Graph.chain_nodes() analysis
    source_mask: np.ndarray  # (n,) bool — Graph.source_chain_nodes() analysis
    dead_mask: np.ndarray  # (n,) bool — Graph.dead_nodes() analysis
    ident_members: np.ndarray  # (k,) full ids pruned by identical rewiring
    ident_reps: np.ndarray  # (k,) their (core) representatives
    full: Graph  # original graph — reconstruction reads its edges
    d: float  # damping factor baked into contracted weights/bias folds
    contracted_m: int  # weighted core edges emitted by chain contraction
    d_dependent: bool = False  # core weights/bias encode d (edges OR folds)

    @property
    def pruned(self) -> np.ndarray:
        """(n,) bool mask of every vertex the core solve does not iterate."""
        out = self.struct_pruned.copy()
        out[self.ident_members] = True
        return out

    def touched_by(self, delta: "GraphDelta") -> bool:
        """True when an update batch invalidates this plan's baked analyses
        and it must be re-planned (:meth:`from_graph`) instead of patched.

        The rule: an endpoint of any added/deleted edge lands on a **pruned
        vertex** or an **identical-class representative**.  Those are exactly
        the cases where a closed form the plan relies on can break — a chain
        vertex gaining a second in-edge, a dead vertex gaining an escape
        edge, a representative's in-set or out-degree diverging from its
        members'.  Updates confined to ordinary core vertices are always safe
        to :meth:`patched` in place: added edges only *raise* core degrees
        (never creating new chains at their endpoints), deleted edges can at
        worst leave a core vertex that *could now* be pruned — a missed
        optimization, not an error — and every core contribution divides by
        the patched full-graph out-degree, so head-degree changes stay exact.
        """
        if delta.num_ops == 0:
            return False
        hot = self.pruned  # fresh copy (property)
        hot[self.ident_reps] = True
        return bool(hot[delta.touched_vertices()].any())

    def patched(self, g_new: Graph, delta: "GraphDelta") -> "DecompositionPlan":
        """Same analyses, updated graphs — the cheap path when
        :meth:`touched_by` is False (raises otherwise).

        The full graph is swapped for ``g_new`` (reconstruction always reads
        it fresh) and the update batch is replayed on the **core**: every
        endpoint is a core vertex (guaranteed by the ``touched_by`` gate), so
        each edge maps through ``full_to_core`` one-to-one and the core's
        retained full-graph out-degrees shift by the same ±1 as the full
        graph's.  Chain/dead/identical masks, contracted edges, and bias
        folds are all untouched — that is the point: re-baking them is the
        expensive O(n) analysis this method exists to skip.
        """
        if self.touched_by(delta):
            raise ValueError(
                "update touches a pruned vertex or identical-class "
                "representative; re-plan with DecompositionPlan.from_graph")
        if delta.num_ops == 0:
            return dataclasses.replace(self, full=g_new)
        def to_core(pairs: np.ndarray) -> np.ndarray:
            mapped = self.full_to_core[pairs]
            assert mapped.min() >= 0 if mapped.size else True
            return mapped
        core_adds = to_core(delta.added)
        core_dels = to_core(delta.deleted)
        add_w = delta.added_weights
        if self.core.weights is not None and add_w is None:
            add_w = np.ones(core_adds.shape[0], dtype=np.float64)
        core_new, _ = self.core.apply_updates(
            core_adds if core_adds.size else None,
            core_dels if core_dels.size else None,
            add_weights=add_w if self.core.weights is not None else None,
        )
        return dataclasses.replace(self, core=core_new, full=g_new)

    @classmethod
    def from_graph(cls, g: Graph, identical: bool = True, chains: bool = True,
                   dead: bool = True, contract: bool = True,
                   d: float = _DEFAULT_DAMPING) -> "DecompositionPlan":
        n = g.n
        chain_mask = g.chain_nodes() if chains else np.zeros(n, dtype=bool)
        dead_mask = g.dead_nodes() if dead else np.zeros(n, dtype=bool)
        source_mask = (g.source_chain_nodes() if (chains and contract)
                       else np.zeros(n, dtype=bool))
        chainlike = chain_mask | source_mask
        if contract:
            # Weighted-core mode: EVERY chainlike vertex is prunable — runs
            # that re-enter the core are contracted into weighted edges +
            # bias folds below; runs draining into the dead region are
            # already inside the (closed) dead set.
            struct_pruned = chainlike | dead_mask
        else:
            # PR-3 suffix-only closure: a pruned vertex must not feed a core
            # vertex, so drop candidates with an out-edge leaving the set
            # until none remain (the dead set is already closed; chains
            # shrink to the suffixes that drain into it).
            s = chain_mask | dead_mask
            if s.any():
                escaping = np.unique(g.src[s[g.src] & ~s[g.dst]])
                while escaping.size:
                    s[escaping] = False
                    # a member with an edge into a just-removed vertex
                    # escapes too
                    srcs = np.unique(g.src[_concat_ranges(g.in_ptr, escaping)])
                    escaping = srcs[s[srcs]]
            struct_pruned = s

        # Identical rewiring: members of an in-neighbour class share the
        # representative's rank; equal out-degree makes the rewired edge
        # contribution pr(rep)/outdeg(rep) == pr(member)/outdeg(member).
        rewire = np.arange(n, dtype=np.int64)
        ident_members: list[int] = []
        ident_reps: list[int] = []
        if identical and n:
            cls_of = g.in_neighbor_classes()
            order = np.argsort(cls_of, kind="stable")
            bounds = np.flatnonzero(
                np.r_[True, cls_of[order][1:] != cls_of[order][:-1], True]
            )
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                members = order[lo:hi]
                members = members[~struct_pruned[members]]
                if members.size < 2:
                    continue
                rep = int(members[0])
                for m in members[1:]:
                    if g.out_degree[m] == g.out_degree[rep]:
                        ident_members.append(int(m))
                        ident_reps.append(rep)
                        rewire[m] = rep
        ident_members_a = np.asarray(ident_members, dtype=np.int64)
        ident_reps_a = np.asarray(ident_reps, dtype=np.int64)

        pruned = struct_pruned.copy()
        pruned[ident_members_a] = True
        full_to_core = np.full(n, -1, dtype=np.int64)
        core_index = np.flatnonzero(~pruned)
        full_to_core[core_index] = np.arange(core_index.size)

        # Mid-graph chain contraction: walk every maximal chainlike run,
        # carrying the affine closed form pr(c_i) = base·A_i + B_i·pr(u)/od(u)
        # (A_1 = bias(c_1); B_1 = d·w(u→c_1), or 0 for a source-chain run;
        # A_{i+1} = bias(c_{i+1}) + d·w_i·A_i; B_{i+1} = d·w_i·B_i).  A run
        # whose terminal edge c_k→t (weight w_t) lands on a core vertex
        # contributes base·(d·w_t·A_k) — folded into t's bias — plus
        # (d·w_t·B_k)·pr(u)/od(u) — the contracted core edge u→t with weight
        # w_t·B_k.  Runs ending inside the dead region contribute nothing to
        # the core (their members are all dead themselves).
        bias_fold = np.zeros(n, dtype=np.float64)
        extra_src: list[int] = []
        extra_dst: list[int] = []
        extra_w: list[float] = []
        if contract and chainlike.any():
            w_full = g.weights
            beta = g.bias
            out_ptr, out_dst, out_slot = g.out_csr()
            pred = np.full(n, -1, dtype=np.int64)
            cidx = np.flatnonzero(chain_mask)
            pred[cidx] = g.src[g.in_ptr[:-1][cidx]]  # the single in-edge
            starts = np.flatnonzero(
                chainlike & (source_mask | ~chainlike[np.maximum(pred, 0)]))
            for v0 in starts:
                headless = bool(source_mask[v0])
                A = 1.0 if beta is None else float(beta[v0])
                if headless:
                    B = 0.0
                else:
                    w0 = 1.0 if w_full is None else float(w_full[g.in_ptr[v0]])
                    B = d * w0
                v = int(v0)
                while True:
                    j = out_ptr[v]  # outdeg-1: the single out-edge
                    succ = int(out_dst[j])
                    w_out = 1.0 if w_full is None else float(w_full[out_slot[j]])
                    if chainlike[succ]:
                        A = (1.0 if beta is None else float(beta[succ])) \
                            + d * w_out * A
                        B = d * w_out * B
                        v = succ
                        continue
                    break
                if struct_pruned[succ]:
                    continue  # run drains into the dead region
                # a chain-fed vertex is always a singleton identical class
                # (its outdeg-1 feeder can appear in no other in-set), so the
                # terminal is a core vertex, never a pruned identical member
                assert full_to_core[succ] >= 0, (v0, succ)
                bias_fold[succ] += d * w_out * A
                if not headless:
                    u = int(pred[v0])
                    hu = int(rewire[u])
                    assert full_to_core[hu] >= 0, (v0, u, hu)
                    extra_src.append(hu)
                    extra_dst.append(succ)
                    extra_w.append(w_out * B)

        if pruned.any():
            # Keep edges between core vertices (rewiring identical-member
            # sources); edges OUT of the struct-pruned set are dropped — a
            # chain terminal's edge into the core is replaced by the
            # contracted weighted edge / bias fold built above.
            keep = ~pruned[g.dst] & ~struct_pruned[g.src]
            src2 = rewire[g.src[keep]]
            csrc = full_to_core[src2]
            cdst = full_to_core[g.dst[keep]]
            weights: Optional[np.ndarray] = None
            if g.weights is not None or extra_w:
                kept_w = (g.weights[keep] if g.weights is not None
                          else np.ones(csrc.size, dtype=np.float64))
                weights = np.r_[kept_w, np.asarray(extra_w, dtype=np.float64)]
            if extra_src:
                csrc = np.r_[csrc, full_to_core[np.asarray(extra_src)]]
                cdst = np.r_[cdst, full_to_core[np.asarray(extra_dst)]]
            core_bias: Optional[np.ndarray] = None
            if g.bias is not None or bias_fold.any():
                core_bias = (g.bias[core_index].copy() if g.bias is not None
                             else np.ones(core_index.size, dtype=np.float64))
                core_bias += bias_fold[core_index]
            core = Graph.from_edges(
                int(core_index.size),
                csrc.astype(np.int32),
                cdst.astype(np.int32),
                weights=weights,
                bias=core_bias,
            )
            # contributions divide by the FULL graph's out-degree: a core
            # vertex keeps leaking mass to its pruned out-neighbours.
            core.out_degree = g.out_degree[core_index].copy()
        else:
            core = g
        return cls(
            n=n, core=core, core_index=core_index, full_to_core=full_to_core,
            struct_pruned=struct_pruned, chain_mask=chain_mask,
            source_mask=source_mask, dead_mask=dead_mask,
            ident_members=ident_members_a, ident_reps=ident_reps_a, full=g,
            d=float(d), contracted_m=len(extra_w),
            d_dependent=bool(extra_w) or bool(bias_fold.any()),
        )

    def stats(self) -> dict:
        """Preprocessing payoff counters (printed by the launcher, recorded
        by ``bench_variants --json``).  Vertex counts split by analysis
        (``pruned_chain`` covers headed *and* source chains); edge counters
        record how much per-iteration edge work the plan removed:
        ``pruned_edges`` is the number of full-graph edges absent from the
        core, ``contracted_edges`` the weighted edges chain contraction
        added in their place (``core_m = full_m - pruned_edges +
        contracted_edges``)."""
        n_ident = int(self.ident_members.size)
        chainlike = self.chain_mask | self.source_mask
        chain = int((self.struct_pruned & chainlike).sum())
        dead = int((self.struct_pruned & ~chainlike).sum())
        return {
            "full_n": self.n,
            "full_m": self.full.m,
            "core_n": self.core.n,
            "core_m": self.core.m,
            "pruned_identical": n_ident,
            "pruned_chain": chain,
            "pruned_dead": dead,
            "pruned_edges": self.full.m + self.contracted_m - self.core.m,
            "contracted_edges": self.contracted_m,
        }

    def reconstruct(self, core_pr, d: float = 0.85,
                    handle_dangling: bool = False) -> np.ndarray:
        """Restore the full-length rank vector from the core solution.

        ``core_pr`` is the inner solve of :attr:`core` run with its own
        ``(1-d)/n_core`` base and ``handle_dangling=False``.  Steps: rescale
        to the full-graph base by linearity, copy identical members from
        their representatives, back-propagate chain/dead ranks in topological
        waves (each wave computes every pruned vertex whose in-neighbours are
        all known — contracted chain interiors reconstruct here too, wave by
        wave down each chain), and finally — iff ``handle_dangling`` —
        rescale by the closed-form redistribution factor
        ``base/(base − (d/n)·Σ_dangling pr)`` (plain L1 normalisation on
        unweighted graphs, still exact on weighted ones).
        """
        g = self.full
        n = self.n
        if self.d_dependent and not np.isclose(d, self.d):
            raise ValueError(
                f"plan was contracted for d={self.d} but reconstruct got "
                f"d={d}; re-plan with DecompositionPlan.from_graph(..., d={d})"
            )
        if handle_dangling and g.bias is not None:
            raise ValueError(
                "closed-form dangling redistribution (L1 normalisation) "
                "requires a uniform full-graph teleport; solve the biased "
                "graph with handle_dangling=False"
            )
        pr = np.zeros(n, dtype=np.float64)
        if n == 0:
            return pr
        core_pr = np.asarray(core_pr, dtype=np.float64)
        if core_pr.shape != (self.core.n,):
            raise ValueError(
                f"core_pr has shape {core_pr.shape}, expected ({self.core.n},)"
            )
        if self.core.n:
            pr[self.core_index] = core_pr * (self.core.n / n)
        pr[self.ident_members] = pr[self.ident_reps]

        inv_out, _ = inv_out_and_dangling(g.out_degree)
        w_full = g.weights  # reconstruction honours weighted input graphs
        beta = g.bias
        base = (1.0 - d) / n
        # Kahn topological pass: unknown_in counts in-edges from not-yet-
        # computed (struct-pruned) sources; a vertex is ready at zero, and
        # completing it decrements its successors — each edge touched once.
        struct = self.struct_pruned
        unknown_in = np.bincount(g.dst[struct[g.src]], minlength=n)
        done = np.zeros(n, dtype=bool)
        n_done = 0
        out_ptr, out_dst, _ = g.out_csr()
        ready = np.flatnonzero(struct & (unknown_in == 0))
        while ready.size:
            idx = _concat_ranges(g.in_ptr, ready)
            srcs = g.src[idx]
            lens = g.in_ptr[ready + 1] - g.in_ptr[ready]
            seg = np.repeat(np.arange(ready.size), lens)
            vals = pr[srcs] * inv_out[srcs]
            if w_full is not None:
                vals = vals * w_full[idx]
            acc = np.bincount(seg, weights=vals, minlength=ready.size)
            pr[ready] = base * (beta[ready] if beta is not None else 1.0) \
                + d * acc
            done[ready] = True
            n_done += ready.size
            succ = out_dst[_concat_ranges(out_ptr, ready)]
            np.subtract.at(unknown_in, succ, 1)
            touched = np.unique(succ)
            ready = touched[struct[touched] & ~done[touched]
                            & (unknown_in[touched] == 0)]
        if n_done != int(struct.sum()):
            raise AssertionError(
                "decomposition reconstruction stalled: pruned set has a "
                "cycle (chain_nodes/dead_nodes invariant violated)"
            )
        if handle_dangling:
            # Closed-form redistribution: the redistributed fixed point is
            # q = c·pr with c = base/(base − (d/n)·Σ_dangling pr) — substitute
            # q = c·pr into q = base·1 + d·W·q + (d/n)(Σ_dang q)·1 to see c.
            # On unweighted graphs c = 1/‖pr‖₁ (unit redistributed mass), but
            # the scalar form also stays exact when per-edge weights < 1 leak
            # mass, where plain L1 normalisation would not.
            dang_mass = pr[g.out_degree == 0].sum()
            denom = base - (d / n) * dang_mass
            if denom > 0:
                pr = pr * (base / denom)
        return pr


@dataclasses.dataclass
class BlockedCOO:
    """2-D edge blocking for the Pallas SpMV kernel.

    Edges are bucketed by (dst_block, src_block) and each bucket is split into
    fixed-capacity tiles.  A tile stores local (within-block) src/dst indices
    so the kernel only addresses one VMEM-resident slice of the rank vector
    and one dst-block accumulator.  Invalid (padding) lanes point at slot 0
    with weight 0.

    ``tiles_weight`` carries per-edge weights in the same tile layout (0 on
    padding lanes) when the source graph is weighted, and is ``None``
    otherwise — the kernels then reuse ``tiles_valid`` as the weight operand,
    so the unweighted path streams no extra VMEM bytes.
    """

    n: int
    block: int  # vertices per block (both axes)
    n_blocks: int
    tiles_src_local: np.ndarray  # (T, cap) int32
    tiles_dst_local: np.ndarray  # (T, cap) int32
    tiles_valid: np.ndarray  # (T, cap) float32 {0,1}
    tile_src_block: np.ndarray  # (T,) int32
    tile_dst_block: np.ndarray  # (T,) int32
    tiles_weight: Optional[np.ndarray] = None  # (T, cap) float32, 0 = padding

    @property
    def num_tiles(self) -> int:
        return int(self.tiles_src_local.shape[0])

    def occupancy(self) -> dict:
        """Tile-occupancy counters of this built layout — see
        :func:`tile_occupancy_stats` for the field meanings."""
        valid = np.asarray(self.tiles_valid)
        return tile_occupancy_stats(
            n_edges=int(valid.sum()),
            n_tiles=self.num_tiles,
            tile_cap=int(valid.shape[1]) if valid.ndim == 2 else 0,
        )


def tile_occupancy_stats(n_edges: int, n_tiles: int, tile_cap: int) -> dict:
    """Occupancy summary of a BlockedCOO layout: ``occupancy`` is valid
    entries / total tile capacity — the fraction of kernel lanes doing real
    edge work (the rest is padding the MXU still pays for).  Build-time
    vertex reordering exists to raise this number; ``bench_variants --json``
    records it per blocked layout so the win is measured, not asserted."""
    cap_total = n_tiles * tile_cap
    return {
        "n_edges": int(n_edges),
        "n_tiles": int(n_tiles),
        "tile_cap": int(tile_cap),
        "occupancy": float(n_edges / cap_total) if cap_total else 0.0,
        "mean_fill": float(n_edges / n_tiles) if n_tiles else 0.0,
    }


def block_pair_counts(g: Graph, block: int, chunk_edges: int = 1 << 20
                      ) -> tuple[np.ndarray, np.ndarray]:
    """The graph's ``(dst_block, src_block)`` edge histogram at ``block``:
    the sorted keys ``dst_block · n_blocks + src_block`` of the non-empty
    pairs and their edge counts.

    One pass over :meth:`Graph.edge_chunks`; a chunk contributes at most
    its distinct pairs, folded together at the end, so peak memory stays
    O(chunk_edges + pairs) for stores far larger than RAM."""
    n_blocks = -(-g.n // block)
    key_parts: list[np.ndarray] = []
    cnt_parts: list[np.ndarray] = []
    for _, src, dst, _ in g.edge_chunks(chunk_edges):
        bucket = (dst // block).astype(np.int64) * n_blocks + (src // block)
        uniq, cnt = np.unique(bucket, return_counts=True)
        key_parts.append(uniq)
        cnt_parts.append(cnt)
    if not key_parts:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    keys, inv = np.unique(np.concatenate(key_parts), return_inverse=True)
    counts = np.zeros(keys.shape[0], dtype=np.int64)
    np.add.at(counts, inv, np.concatenate(cnt_parts))
    return keys, counts


def tiles_from_counts(keys: np.ndarray, counts: np.ndarray, n_blocks: int,
                      tile_cap: int) -> int:
    """The tiles :func:`build_blocked_coo` makes from a block-pair histogram
    (:func:`block_pair_counts`): ``ceil(count / tile_cap)`` per pair, plus
    one coverage tile per dst block no pair touches (the kernel initializes
    every output run)."""
    covered = np.unique(keys // n_blocks).shape[0]
    return int((-(-counts // tile_cap)).sum()) + n_blocks - covered


def blocked_tile_stats(g: Graph, block: int = 256, tile_cap: int = 1024,
                       chunk_edges: int = 1 << 20) -> dict:
    """Streaming :class:`BlockedCOO` occupancy — **without building tiles**,
    from the block-pair histogram (:func:`block_pair_counts`), so the layout
    stage of the out-of-core pipeline can derive occupancy for stores far
    larger than RAM."""
    n_blocks = -(-g.n // block)
    keys, counts = block_pair_counts(g, block, chunk_edges)
    n_tiles = tiles_from_counts(keys, counts, n_blocks, tile_cap)
    stats = tile_occupancy_stats(g.m, n_tiles, tile_cap)
    stats.update(block=block, n_blocks=n_blocks, n_buckets=int(keys.shape[0]))
    return stats


def build_blocked_coo(g: Graph, block: int = 512, tile_cap: int = 2048) -> BlockedCOO:
    n_blocks = -(-g.n // block)
    weighted = g.weights is not None
    if n_blocks == 0:  # empty graph: no vertices, no tiles
        empty = np.zeros((0, tile_cap), dtype=np.int32)
        return BlockedCOO(
            n=g.n, block=block, n_blocks=0,
            tiles_src_local=empty, tiles_dst_local=empty.copy(),
            tiles_valid=np.zeros((0, tile_cap), dtype=np.float32),
            tile_src_block=np.zeros((0,), dtype=np.int32),
            tile_dst_block=np.zeros((0,), dtype=np.int32),
            tiles_weight=(np.zeros((0, tile_cap), dtype=np.float32)
                          if weighted else None),
        )
    sb = g.src // block
    db = g.dst // block
    bucket = db.astype(np.int64) * n_blocks + sb
    order = np.argsort(bucket, kind="stable")
    src_s, dst_s, bucket_s = g.src[order], g.dst[order], bucket[order]
    w_s = g.weights[order].astype(np.float32) if weighted else None

    tiles_src, tiles_dst, tiles_val, tiles_wt, t_sb, t_db = [], [], [], [], [], []
    if bucket_s.size:
        starts = np.flatnonzero(np.r_[True, bucket_s[1:] != bucket_s[:-1]])
    else:  # zero-edge graph: no buckets, only the coverage tiles below
        starts = np.zeros((0,), dtype=np.int64)
    ends = np.r_[starts[1:], bucket_s.size]
    for s, e in zip(starts, ends):
        b = bucket_s[s]
        dblk, sblk = divmod(int(b), n_blocks)
        for ts in range(s, e, tile_cap):
            te = min(ts + tile_cap, e)
            k = te - ts
            sl = np.zeros(tile_cap, dtype=np.int32)
            dl = np.zeros(tile_cap, dtype=np.int32)
            vl = np.zeros(tile_cap, dtype=np.float32)
            sl[:k] = src_s[ts:te] - sblk * block
            dl[:k] = dst_s[ts:te] - dblk * block
            vl[:k] = 1.0
            tiles_src.append(sl)
            tiles_dst.append(dl)
            tiles_val.append(vl)
            if weighted:
                wl = np.zeros(tile_cap, dtype=np.float32)
                wl[:k] = w_s[ts:te]
                tiles_wt.append(wl)
            t_sb.append(sblk)
            t_db.append(dblk)

    # Every dst block needs >=1 tile so the kernel initializes its output run.
    covered = set(t_db)
    for dblk in range(n_blocks):
        if dblk not in covered:
            tiles_src.append(np.zeros(tile_cap, np.int32))
            tiles_dst.append(np.zeros(tile_cap, np.int32))
            tiles_val.append(np.zeros(tile_cap, np.float32))
            if weighted:
                tiles_wt.append(np.zeros(tile_cap, np.float32))
            t_sb.append(0)
            t_db.append(dblk)

    # kernel contract: tiles sorted by dst_block (contiguous output runs)
    order2 = np.argsort(np.asarray(t_db), kind="stable")
    tiles_src = [tiles_src[i] for i in order2]
    tiles_dst = [tiles_dst[i] for i in order2]
    tiles_val = [tiles_val[i] for i in order2]
    if weighted:
        tiles_wt = [tiles_wt[i] for i in order2]
    t_sb = [t_sb[i] for i in order2]
    t_db = [t_db[i] for i in order2]

    return BlockedCOO(
        n=g.n,
        block=block,
        n_blocks=n_blocks,
        tiles_src_local=np.stack(tiles_src),
        tiles_dst_local=np.stack(tiles_dst),
        tiles_valid=np.stack(tiles_val),
        tile_src_block=np.asarray(t_sb, dtype=np.int32),
        tile_dst_block=np.asarray(t_db, dtype=np.int32),
        tiles_weight=np.stack(tiles_wt) if weighted else None,
    )


def patch_blocked_coo(coo: BlockedCOO, g: Graph,
                      delta: GraphDelta) -> BlockedCOO:
    """Patch a built :class:`BlockedCOO` after :meth:`Graph.apply_updates`:
    rebuild only the tiles of dst blocks the delta touched, keep every other
    tile verbatim.

    ``g`` is the post-update graph and ``delta`` the record the update
    returned.  The result is **array-identical** to a full
    :func:`build_blocked_coo` of ``g`` (tests assert equality, not closeness):
    a dst block's edges are one contiguous slice of the dst-sorted arrays, so
    untouched blocks' tiles cannot have changed, and within a touched block
    the tiles are re-emitted in the same src-block-major order (plus the
    same coverage tile when the block went empty) the full build uses.
    Work is O(edges in touched blocks + total tiles), independent of ``m``
    for localized updates.
    """
    if g.n != coo.n:
        raise ValueError(
            f"apply_updates never changes n: layout has n={coo.n}, "
            f"graph has n={g.n}")
    weighted = g.weights is not None
    if weighted != (coo.tiles_weight is not None):
        raise ValueError(
            "graph and layout disagree on weightedness; rebuild the layout")
    block = coo.block
    n_blocks = coo.n_blocks
    touched = delta.touched_dst_blocks(block)
    if touched.size == 0 or n_blocks == 0:
        return coo
    tile_cap = int(coo.tiles_src_local.shape[1])
    keep = ~np.isin(np.asarray(coo.tile_dst_block), touched)

    new_src, new_dst, new_val, new_wt = [], [], [], []
    new_sb, new_db = [], []
    for dblk in touched:
        lo = int(g.in_ptr[dblk * block])
        hi = int(g.in_ptr[min((dblk + 1) * block, g.n)])
        src_s = np.asarray(g.src[lo:hi])
        dst_s = np.asarray(g.dst[lo:hi])
        w_s = np.asarray(g.weights[lo:hi]) if weighted else None
        sb = src_s // block
        # stable sort by src block == the full build's global stable bucket
        # sort restricted to this dst block (bucket id is dst-block-major)
        order = np.argsort(sb, kind="stable")
        src_s, dst_s, sb = src_s[order], dst_s[order], sb[order]
        if weighted:
            w_s = w_s[order].astype(np.float32)
        if sb.size:
            starts = np.flatnonzero(np.r_[True, sb[1:] != sb[:-1]])
        else:
            starts = np.zeros(0, dtype=np.int64)
        ends = np.r_[starts[1:], sb.size]
        emitted = False
        for s, e in zip(starts, ends):
            sblk = int(sb[s])
            for ts in range(s, e, tile_cap):
                te = min(ts + tile_cap, e)
                k = te - ts
                sl = np.zeros(tile_cap, dtype=np.int32)
                dl = np.zeros(tile_cap, dtype=np.int32)
                vl = np.zeros(tile_cap, dtype=np.float32)
                sl[:k] = src_s[ts:te] - sblk * block
                dl[:k] = dst_s[ts:te] - int(dblk) * block
                vl[:k] = 1.0
                new_src.append(sl)
                new_dst.append(dl)
                new_val.append(vl)
                if weighted:
                    wl = np.zeros(tile_cap, dtype=np.float32)
                    wl[:k] = w_s[ts:te]
                    new_wt.append(wl)
                new_sb.append(sblk)
                new_db.append(int(dblk))
                emitted = True
        if not emitted:  # block went empty: keep the coverage-tile invariant
            new_src.append(np.zeros(tile_cap, np.int32))
            new_dst.append(np.zeros(tile_cap, np.int32))
            new_val.append(np.zeros(tile_cap, np.float32))
            if weighted:
                new_wt.append(np.zeros(tile_cap, np.float32))
            new_sb.append(0)
            new_db.append(int(dblk))

    def merged(kept: np.ndarray, fresh: list, dtype) -> np.ndarray:
        fresh_a = (np.stack(fresh) if fresh
                   else np.zeros((0,) + kept.shape[1:], dtype))
        return np.concatenate([np.asarray(kept)[keep], fresh_a])

    t_db = merged(coo.tile_dst_block, [np.int32(x) for x in new_db], np.int32)
    # a dst block's tiles are wholly kept or wholly fresh, so a stable sort
    # by dst block restores exactly the full build's tile order
    order2 = np.argsort(t_db, kind="stable")
    return BlockedCOO(
        n=coo.n,
        block=block,
        n_blocks=n_blocks,
        tiles_src_local=merged(coo.tiles_src_local, new_src, np.int32)[order2],
        tiles_dst_local=merged(coo.tiles_dst_local, new_dst, np.int32)[order2],
        tiles_valid=merged(coo.tiles_valid, new_val, np.float32)[order2],
        tile_src_block=merged(
            coo.tile_src_block, [np.int32(x) for x in new_sb], np.int32
        )[order2],
        tile_dst_block=t_db[order2],
        tiles_weight=(merged(coo.tiles_weight, new_wt, np.float32)[order2]
                      if weighted else None),
    )
