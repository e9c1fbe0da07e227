"""Dynamic incremental PageRank: edge-stream updates with delta-push repair.

The maintained object is a pair ``(est, resid)`` over the *current* graph
with the Neumann-series invariant

    pr* = est + (I − d·Mᵀ)⁻¹ · resid

where ``pr*`` is the exact (float64, leaky-convention) fixed point and
``resid`` is the **signed** rank defect ``(base·bias + d·Mᵀ·est) − est``.
Because ``‖(I − d·Mᵀ)⁻¹‖₁ ≤ 1/(1−d)`` for a substochastic ``M``, the
quantity

    ‖pr* − est‖₁  ≤  Σ_v |resid[v]| / (1 − d)

is an **a-posteriori L1 certificate** available at any time without knowing
``pr*`` — the dynamic analogue of the forward-push bound in
:mod:`repro.ppr.push` (Zhang et al., arXiv:2302.03245).

An edge-batch update ``(adds, dels)`` changes only the columns of ``M``
belonging to sources whose out-edge set changed (``delta.touched_src`` — an
out-degree change rescales the whole column), so the residual is repaired
*locally* in O(Σ deg(touched)) instead of recomputed:

    resid += d · (M_newᵀ − M_oldᵀ) · est

Then a signed forward-push pass (:func:`repro.ppr.push.push_residual` with
``bank=1.0`` — the Neumann identity banks the residual whole, unlike the
PPR loop's ``1−d``) drains ``resid`` until the certificate meets ``tol``.
Pushes decay by ``d`` per hop and die at dangling vertices, so updates
whose perturbation is near sinks stay local; when the cascade goes global
(or ``max_push_rounds`` is exhausted) the engine *falls back* to a warm
global solve — any registry variant, seeded with the current estimate via
the ``pr0`` transport option — and re-certifies with an exact float64
residual plus a refinement push pass.  Kollias et al.'s asynchronous-
iteration analysis (PAPERS.md, cs/0606047) is what makes warm starts sound:
the fixed point does not depend on the starting vector.

STIC-D plan caching rides along: when the configured variant is
plan-staged, the engine keeps the baked :class:`DecompositionPlan` across
updates, *patching* it (cheap core replay) while no update endpoint touches
a pruned/contracted vertex and re-baking it only when one does.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.solver import (
    DEFAULT_DAMPING,
    PlannedBundle,
    build_variant,
    warm_start_pr,
)
from repro.graphs.csr import Graph, _concat_ranges

__all__ = [
    "IncrementalPageRank",
    "UpdateReport",
    "exact_residual",
    "make_update_injector",
    "random_update_batch",
]


def exact_residual(g: Graph, est: np.ndarray, *,
                   d: float = DEFAULT_DAMPING) -> np.ndarray:
    """Signed float64 rank defect ``(base·bias + d·Mᵀ·est) − est`` of an
    estimate against graph ``g`` (leaky dangling convention — matches the
    engine's maintained invariant).  Zero exactly at the fixed point."""
    n = int(g.n)
    est = np.asarray(est, dtype=np.float64)
    if est.shape != (n,):
        raise ValueError(f"est must have shape ({n},), got {est.shape}")
    if n == 0:
        return est.copy()
    return warm_start_pr(g, est, d=d, handle_dangling=False) - est


def _column_correction(r: np.ndarray, g: Graph, delta_or_src, est: np.ndarray,
                       d: float, sign: float) -> None:
    """Accumulate ``sign · d · Mᵀ(g)|cols · est`` into ``r`` for the columns
    in ``delta_or_src`` (a :class:`GraphDelta`'s ``touched_src`` or an index
    array) — the per-side half of ``resid += d(M_new−M_old)ᵀ est``."""
    us = np.asarray(delta_or_src, dtype=np.int64)
    if us.size == 0:
        return
    out_ptr, out_dst, out_slot = g.out_csr()
    deg = g.out_degree.astype(np.int64)[us]
    live = deg > 0
    if not live.any():
        return
    ul, dl = us[live], deg[live]
    eidx = _concat_ranges(out_ptr, ul)
    vals = np.repeat(sign * d * est[ul] / dl, dl)
    if g.weights is not None:
        vals = vals * g.weights[out_slot][eidx]
    np.add.at(r, out_dst[eidx], vals)


@dataclasses.dataclass
class UpdateReport:
    """What one :meth:`IncrementalPageRank.apply` batch cost and certified.

    ``mode`` is ``"push"`` (local delta-push repair met the certificate),
    ``"fallback"`` (warm global solve + refinement pass), or ``"noop"``
    (empty batch).  ``touched``/``touched_frac`` count vertices the repair
    pushed or scattered into — the locality metric (a fallback touches
    everything by definition).  ``l1_cert`` is the a-posteriori bound on
    ``‖pr* − est‖₁`` after the batch; ``converged`` says it met ``tol``.
    """

    mode: str
    num_ops: int
    rounds: int = 0
    pushes: int = 0
    touched: int = 0
    touched_frac: float = 0.0
    l1_cert: float = 0.0
    converged: bool = True
    plan_action: str = "none"  # "none" | "patched" | "invalidated"


class IncrementalPageRank:
    """Maintains certified PageRank over an evolving graph.

    >>> ipr = IncrementalPageRank(g, tol=1e-8)
    >>> rep = ipr.apply(adds=[[3, 7]], dels=[[0, 5]])
    >>> ipr.pagerank        # repaired ranks, ‖pr* − est‖₁ ≤ ipr.certificate

    ``variant`` names the registry solver used for the *initial* solve and
    any fallback; its bundle is rebuilt lazily after updates (for the
    plan-staged STIC-D variants the decomposition plan is patched across
    updates and only re-baked when an update touches a pruned/contracted
    vertex — see :meth:`DecompositionPlan.touched_by`).

    Only the leaky convention (``handle_dangling=False``) is supported: the
    redistribution term makes every column of the iteration matrix dense in
    the dangling rows, which destroys the locality the repair relies on.
    (The redistributed fixed point is a closed-form rescale of the leaky one
    on unweighted graphs — recover it downstream if needed.)
    """

    def __init__(self, g: Graph, *, variant: str = "sequential",
                 d: float = DEFAULT_DAMPING, tol: float = 1e-8,
                 max_push_rounds: int = 10_000,
                 handle_dangling: bool = False, **opts):
        if handle_dangling:
            raise NotImplementedError(
                "IncrementalPageRank supports only the leaky convention "
                "(handle_dangling=False); dangling redistribution is dense "
                "and defeats local repair")
        self.g = g
        self.variant = variant
        self.d = float(d)
        self.tol = float(tol)
        self.max_push_rounds = int(max_push_rounds)
        self.opts = dict(opts)
        self._variant_obj, self._bundle = build_variant(
            variant, g, d=self.d, **self.opts)
        self._plan = None
        self._template = None
        if isinstance(self._bundle, PlannedBundle):
            self._plan = self._bundle.plan
            self._template = self._bundle
        res = self._variant_obj.run(
            self._bundle, d=self.d, threshold=self.tol, max_iter=100_000,
            handle_dangling=False, **self.opts)
        self.est = np.asarray(res.pr, dtype=np.float64).copy()
        self.resid = exact_residual(g, self.est, d=self.d)
        # float32 variants converge to a certificate floor above a tight
        # tol; one refinement pass in float64 closes the gap up front
        self._refine()

    # -- public state ------------------------------------------------------

    @property
    def n(self) -> int:
        return int(self.g.n)

    @property
    def pagerank(self) -> np.ndarray:
        """Current rank estimate (float64).  ``‖pr* − est‖₁`` is bounded by
        :attr:`certificate`."""
        return self.est

    @property
    def certificate(self) -> float:
        """A-posteriori bound on ``‖pr* − est‖₁`` = ``Σ|resid|/(1−d)``."""
        return float(np.abs(self.resid).sum() / (1.0 - self.d))

    # -- internals ---------------------------------------------------------

    @property
    def _target(self) -> float:
        return (1.0 - self.d) * self.tol  # certificate ≤ tol ⇔ Σ|r| ≤ this

    def _refine(self, touched: np.ndarray | None = None) -> tuple[int, int]:
        """One signed drain pass at ``rmax`` small enough that full drainage
        guarantees the certificate (``n·rmax ≤ target/2``)."""
        from repro.ppr.push import push_residual

        rmax = self._target / (2.0 * max(self.n, 1))
        return push_residual(
            self.g, self.est, self.resid, d=self.d, rmax=rmax, bank=1.0,
            signed=True, handle_dangling=False,
            max_rounds=self.max_push_rounds, touched=touched)

    def _ensure_bundle(self):
        if self._bundle is None:
            if self._plan is not None and self._template is not None:
                # patched plan survives: re-bake only the inner core bundle
                inner = (self._template.inner.build(
                    self._plan.core, **self._template.build_opts)
                    if self._plan.core.n else None)
                self._bundle = dataclasses.replace(
                    self._template, plan=self._plan, bundle=inner)
                self._template = self._bundle
            else:
                self._variant_obj, self._bundle = build_variant(
                    self.variant, self.g, d=self.d, **self.opts)
                if isinstance(self._bundle, PlannedBundle):
                    self._plan = self._bundle.plan
                    self._template = self._bundle
        return self._variant_obj, self._bundle

    # -- the update path ---------------------------------------------------

    def apply(self, adds=None, dels=None, add_weights=None) -> UpdateReport:
        """Apply one edge batch (deletes first, then adds — see
        :meth:`Graph.apply_updates`), repair the ranks, and certify."""
        g_old = self.g
        g_new, delta = g_old.apply_updates(adds=adds, dels=dels,
                                           add_weights=add_weights)
        if delta.num_ops == 0:
            return UpdateReport(mode="noop", num_ops=0,
                                l1_cert=self.certificate)

        plan_action = "none"
        if self._plan is not None:
            if self._plan.touched_by(delta):
                self._plan = None  # re-baked lazily on next fallback
                plan_action = "invalidated"
            else:
                self._plan = self._plan.patched(g_new, delta)
                plan_action = "patched"
        self._bundle = None  # stale for g_new either way

        # local residual correction: resid += d(M_new − M_old)ᵀ est over the
        # touched columns only — O(Σ deg) of the changed sources
        _column_correction(self.resid, g_old, delta.touched_src, self.est,
                           self.d, sign=-1.0)
        _column_correction(self.resid, g_new, delta.touched_src, self.est,
                           self.d, sign=+1.0)
        self.g = g_new

        touched = np.zeros(self.n, dtype=bool)
        touched[delta.touched_vertices()] = True
        rounds, pushes = self._refine(touched=touched)
        if float(np.abs(self.resid).sum()) <= self._target:
            return UpdateReport(
                mode="push", num_ops=delta.num_ops, rounds=rounds,
                pushes=pushes, touched=int(touched.sum()),
                touched_frac=float(touched.sum()) / max(self.n, 1),
                l1_cert=self.certificate, converged=True,
                plan_action=plan_action)

        # fallback: warm global solve from the (partially repaired)
        # estimate, then exact residual + refinement pass to re-certify
        v, bundle = self._ensure_bundle()
        res = v.run(bundle, d=self.d, threshold=self.tol, max_iter=100_000,
                    handle_dangling=False, pr0=self.est, **self.opts)
        self.est = np.asarray(res.pr, dtype=np.float64).copy()
        self.resid = exact_residual(self.g, self.est, d=self.d)
        r2, p2 = self._refine()
        cert = self.certificate
        return UpdateReport(
            mode="fallback", num_ops=delta.num_ops, rounds=rounds + r2,
            pushes=pushes + p2, touched=self.n, touched_frac=1.0,
            l1_cert=cert, converged=cert <= self.tol,
            plan_action=plan_action)


# ---------------------------------------------------------------------------
# Update-stream generation (tests + benchmarks)
# ---------------------------------------------------------------------------


def random_update_batch(
    g: Graph,
    rng: np.random.Generator,
    n_ops: int,
    *,
    frac_adds: float = 0.5,
    localized: bool = False,
) -> tuple[np.ndarray | None, np.ndarray | None]:
    """Sample one valid ``(adds, dels)`` batch against the *current* graph.

    ``localized=False`` — uniform stream: deletes are distinct existing
    edges; adds are pairs absent from the surviving edge set (re-adding a
    just-deleted edge is allowed by :meth:`Graph.apply_updates` but not
    generated, keeping batches order-insensitive for the metamorphic tests).

    ``localized=True`` — sink-bounded stream: adds go from a currently
    dangling vertex to another dangling vertex (the new column routes rank
    into a sink, where the push cascade dies in one hop); deletes remove the
    single out-edge of a degree-1 vertex pointing at a sink.  Such deletes
    exist after prior localized adds, so alternating batches sustain the
    stream.  Counts are clamped to the available candidates — callers read
    the returned shapes, not the request.
    """
    n = int(g.n)
    n_adds = int(round(n_ops * frac_adds))
    n_dels = n_ops - n_adds
    src = np.asarray(g.src, dtype=np.int64)
    dst = np.asarray(g.dst, dtype=np.int64)
    key = dst * n + src  # canonical (ascending) edge keys
    outdeg = np.asarray(g.out_degree, dtype=np.int64)

    if localized:
        dang = np.flatnonzero(outdeg == 0)
        cand_del = np.flatnonzero((outdeg[src] == 1) & (outdeg[dst] == 0))
        # one delete per degree-1 source (its only out-edge)
        if cand_del.size:
            _, first = np.unique(src[cand_del], return_index=True)
            cand_del = cand_del[first]
        n_dels = min(n_dels, cand_del.size)
        dels = None
        if n_dels:
            pick = rng.choice(cand_del.size, size=n_dels, replace=False)
            dels = np.stack([src[cand_del[pick]], dst[cand_del[pick]]], axis=1)
        # distinct dangling sources, dangling targets, no self-pairs
        n_adds = min(n_adds, max(dang.size - 1, 0))
        adds = None
        if n_adds:
            us = rng.choice(dang, size=n_adds, replace=False)
            vs = rng.choice(dang, size=n_adds)
            clash = vs == us
            while clash.any():  # re-draw self-pairs (dang.size ≥ 2 here)
                vs[clash] = rng.choice(dang, size=int(clash.sum()))
                clash = vs == us
            adds = np.stack([us, vs], axis=1)
        return adds, dels

    # one slot per distinct edge: deleting a parallel edge's key twice in
    # one batch is refused by apply_updates
    _, distinct = np.unique(key, return_index=True)
    n_dels = min(n_dels, distinct.size)
    dels = None
    surviving = key
    if n_dels:
        pick = distinct[rng.choice(distinct.size, size=n_dels, replace=False)]
        dels = np.stack([src[pick], dst[pick]], axis=1)
        surviving = np.delete(key, pick)
    adds_list: list[np.ndarray] = []
    seen = set()
    need = n_adds
    while need > 0:
        cs = rng.integers(0, n, size=2 * need)
        cd = rng.integers(0, n, size=2 * need)
        ck = cd * n + cs
        pos = np.searchsorted(surviving, ck)
        in_set = pos < surviving.size
        in_set[in_set] = surviving[pos[in_set]] == ck[in_set]
        fresh = ~in_set
        for s, t, k in zip(cs[fresh], cd[fresh], ck[fresh]):
            if k in seen:
                continue
            seen.add(k)
            adds_list.append(np.array([s, t], dtype=np.int64))
            if len(adds_list) == n_adds:
                break
        need = n_adds - len(adds_list)
    adds = np.stack(adds_list) if adds_list else None
    return adds, dels


def make_update_injector(
    rng: np.random.Generator,
    ops_per_batch: int,
    *,
    frac_adds: float = 0.5,
    localized: bool = False,
):
    """Update hook for the serving load generator (``serving/loadgen.py``).

    Batches must be sampled against the *current* graph — each applied
    batch changes what a valid next batch looks like — so the injector is a
    closure the load generator calls with the runtime's live graph at every
    injection point, not a precomputed list: ``injector(g) -> (adds,
    dels)``.  Owns its RNG, so a fixed seed reproduces the whole mid-stream
    update sequence regardless of load timing."""

    def next_batch(g: Graph):
        return random_update_batch(g, rng, ops_per_batch,
                                   frac_adds=frac_adds, localized=localized)

    return next_batch
