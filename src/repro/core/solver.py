"""Single convergence engine + variant registry for every PageRank solver.

The paper's variants differ along exactly two orthogonal axes (this is the
Kollias/Lakhotia factoring — chaotic-relaxation *schedules* are independent of
the *sweep* kernel that applies Eq. (1)):

* the **sweep**: how one unit of rank propagation is computed (vertex-centric
  segment-sum, edge-centric scatter/gather, STIC-D class sharing, blocked
  Pallas SpMV, ...);
* the **schedule**: when a sweep observes other units' writes — ``barrier``
  (Jacobi: every read sees the previous iteration) or ``nosync`` (Gauss–
  Seidel-style: units are swept in order within an iteration and read the
  freshest ranks; the TPU-deterministic member of the paper's admissible
  asynchronous executions, whose fixed point is schedule-independent by
  Lemma 2).

Optional **transforms** (loop perforation, Alg 5) post-process each proposed
update, and a **stop** rule (global threshold + optional thread-level
observed-error termination, Alg 3 l.17-19) closes the loop.  :func:`solve`
owns the single ``jax.lax.while_loop``; no variant hand-rolls its own.

The module also hosts the **variant registry**: each paper variant registers a
``build`` (host graph -> device bundle) and ``run`` (bundle -> result) pair,
so launch scripts, benchmarks, and tests enumerate variants instead of
hard-coding them, and new variants (distributed stale-read modes, perforated
Pallas, ...) are one ``register_variant`` call.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_DAMPING = 0.85


class PageRankResult(NamedTuple):
    """Result of one solve.

    ``pr`` is the rank vector — shape ``(n,)`` for the global variants,
    ``(b, n)`` for the batched personalized (PPR) variants.  ``residuals``,
    when present, is the per-iteration observed-error trajectory recorded by
    :func:`solve` (an ``inf``-padded ``(max_iter,)`` buffer — slice it with
    ``residuals[:iterations]`` host-side); solvers that own their loop (the
    ``shard_map`` distributed modes, the numpy oracle, the push solver) leave
    it ``None``.  ``sweeps`` counts **executed schedule-unit updates** — the
    work metric the adaptive schedules optimize (a skipped partition/block
    costs no sweep): ``iterations`` for the single-unit barrier schedules,
    at most ``iterations · p`` for the partitioned ones; ``None`` for the
    loop-owning solvers — except the push solvers, which report their push
    count here (a push *is* their schedule unit) while leaving ``residuals``
    ``None``.  tests/test_adaptive.py pins this ownership contract for every
    registry variant.
    """

    pr: jax.Array
    iterations: jax.Array
    err: jax.Array
    residuals: Any = None
    sweeps: Any = None


class EngineState(NamedTuple):
    """Loop-carried state of the convergence engine.

    ``pr`` may be any layout (flat vector, padded vector, blocked 2-D) — the
    engine never indexes it, only the schedule's step function does.  ``perr``
    holds the last *observed* error per schedule unit (1 for barrier, p for
    no-sync partitions); for units an adaptive schedule skipped it holds the
    pre-round certified residual bound instead (at or below the skip cut by
    construction, so it never blocks the stop rule).  The stop rule reduces
    over it either way.  ``sweeps`` counts executed unit updates (engine
    telemetry every schedule maintains).  ``aux`` is schedule-owned carried
    state the engine never touches — the adaptive schedules keep their
    staleness-inflated residual-bound vector here; every other schedule
    leaves it the empty-pytree default.
    """

    pr: jax.Array
    frozen: jax.Array  # same shape as pr — perforation freeze mask
    perr: jax.Array  # (n_units,) last observed per-unit error / bound
    it: jax.Array  # int32 iteration counter
    sweeps: jax.Array  # int32 executed schedule-unit updates
    aux: Any = ()  # schedule-owned carried state (empty for most schedules)


# A transform post-processes one proposed update: (old, new, frozen) ->
# (new', frozen').  Applied inside the schedule, per unit.
Transform = Callable[[jax.Array, jax.Array, jax.Array], tuple[jax.Array, jax.Array]]


def perforation(threshold: float) -> Transform:
    """Alg 5 loop perforation: freeze vertices whose delta is tiny but nonzero."""

    def transform(old, new, frozen):
        cut = jnp.asarray(threshold * 1e-5, new.dtype)
        delta = jnp.abs(new - old)
        frozen_new = frozen | ((delta > 0) & (delta < cut))
        return jnp.where(frozen, old, new), frozen_new

    return transform


def row_freeze(threshold: float, axes: tuple[int, ...] = (-1,)) -> Transform:
    """Per-row convergence freeze for **batched** solves (the PPR subsystem).

    A row whose observed delta (max over ``axes`` — the non-batch axes of the
    rank layout) is at or below ``threshold`` is frozen: it holds its
    converged value while other rows keep iterating, which is both the
    per-slot early exit of the serving engine and what keeps warm-started
    rows from drifting.  Unlike :func:`perforation` this is exact, not lossy:
    a converged row of a contraction stays converged, freezing merely sheds
    its work.
    """

    def transform(old, new, frozen):
        new = jnp.where(frozen, old, new)
        row_err = jnp.max(jnp.abs(new - old), axis=axes, keepdims=True)
        frozen_new = frozen | jnp.broadcast_to(row_err <= threshold, frozen.shape)
        return new, frozen_new

    return transform


def _apply_transforms(transforms: Sequence[Transform], old, new, frozen):
    for t in transforms:
        new, frozen = t(old, new, frozen)
    return new, frozen


# ---------------------------------------------------------------------------
# Schedules — combinators turning a sweep fn into one engine step
# ---------------------------------------------------------------------------


def barrier_schedule(sweep: Callable[..., jax.Array],
                     transforms: Sequence[Transform] = (),
                     *, pass_frozen: bool = False) -> Callable:
    """Jacobi: ``sweep(pr)`` proposes a full replacement computed from the
    previous iterate; the data dependence of the while-loop body *is* the
    barrier (paper Alg 1).  One schedule unit.

    ``pass_frozen`` calls ``sweep(pr, frozen)`` instead, for sweeps that can
    exploit the perforation freeze mask *inside* the sweep (e.g. the blocked
    Pallas Gauss–Seidel pass, whose in-pass fresh reads must see frozen
    vertices at their frozen values).  The freeze *decision* still lives in
    the engine's :func:`perforation` transform — the sweep only respects the
    mask, it never updates it.  Requires ``track_frozen=True`` in
    :func:`solve` (otherwise ``frozen`` is a zero-size stub)."""

    def step(state: EngineState) -> EngineState:
        new = sweep(state.pr, state.frozen) if pass_frozen else sweep(state.pr)
        new, frozen = _apply_transforms(transforms, state.pr, new, state.frozen)
        err = jnp.max(jnp.abs(new - state.pr))
        return EngineState(new, frozen, jnp.full_like(state.perr, err),
                           state.it + 1, state.sweeps + 1)

    return step


def batched_barrier_schedule(
    sweep: Callable[..., jax.Array],
    transforms: Sequence[Transform] = (),
    *,
    pass_frozen: bool = False,
    row_error: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
) -> Callable:
    """Jacobi over a **batch** of ``b`` independent solves sharing one graph.

    The rank state is any layout with a batch axis — ``(b, n)`` for the
    vertex-centric sweeps, ``(n_blocks, b, block)`` for the blocked Pallas
    layout — and each batch row is one schedule unit: ``perr`` has shape
    ``(b,)`` (pass ``n_units=b`` to :func:`solve`), so the stop rule fires
    only when *every* row has converged, while a :func:`row_freeze` transform
    exits individual rows early.

    ``row_error(new, old) -> (b,)`` reduces the non-batch axes; the default
    assumes the batch is axis 0 and reduces everything after it.  ``pass_
    frozen`` is as in :func:`barrier_schedule` (the batched Pallas sweep
    takes the freeze mask as a kernel operand).
    """

    def step(state: EngineState) -> EngineState:
        new = sweep(state.pr, state.frozen) if pass_frozen else sweep(state.pr)
        new, frozen = _apply_transforms(transforms, state.pr, new, state.frozen)
        if row_error is not None:
            err = row_error(new, state.pr)
        else:
            err = jnp.max(jnp.abs(new - state.pr),
                          axis=tuple(range(1, new.ndim)))
        return EngineState(new, frozen, err, state.it + 1, state.sweeps + 1)

    return step


def nosync_schedule(
    sweep: Callable[..., jax.Array],
    *,
    p: int,
    vp: int,
    threshold: float,
    transforms: Sequence[Transform] = (),
    thread_level: bool = False,
    prologue: Callable[[jax.Array], Any] | None = None,
) -> Callable:
    """No-Sync (paper Alg 3): partitions are swept **in order within an
    iteration**, each reading the freshest ranks (single ``pr`` array, no
    prev/new swap).  ``sweep(i, pr)`` returns partition ``i``'s proposed
    ``(vp,)`` block from the current full vector.  Partitions live on the
    **last** axis of ``pr``, so the same schedule drives both the global
    ``(n_pad,)`` layout and the batched PPR ``(b, n_pad)`` layout (every row
    of a batch shares the partition sweep order; per-unit error reduces over
    the batch too).

    ``prologue(pr)``, when given, computes once-per-iteration context shared
    by every partition sweep — e.g. the dangling-mass snapshot, which would
    otherwise cost a full-vector reduction *per partition* — and the sweep is
    called as ``sweep(i, pr, ctx)`` instead.  Iteration-level freshness keeps
    the fixed point unchanged (Lemma 2: it is stationary there).

    ``thread_level`` wires the paper's thread-level convergence (Alg 3
    l.17-19) as *termination semantics*: a unit skips its sweep only when it
    OBSERVES every unit's last error at or below threshold — never on its own
    error alone (skipping on the local error freezes partitions whose inputs
    change later and converges to a wrong fixed point; the paper reports the
    same phenomenon for No-Sync-Edge §4.4).  Since the engine's stop rule
    fires on the same observation, this only sheds the tail of the final
    iteration and cannot change the fixed point.
    """

    def step(state: EngineState) -> EngineState:
        ctx = prologue(state.pr) if prologue is not None else None

        def sweep_partition(i, carry):
            def do(carry):
                pr, frozen, perr, nsw = carry
                ax = pr.ndim - 1  # partitions live on the last axis
                old = jax.lax.dynamic_slice_in_dim(pr, i * vp, vp, axis=ax)
                new = sweep(i, pr) if prologue is None else sweep(i, pr, ctx)
                if transforms:  # frozen is a zero-size stub otherwise
                    fr = jax.lax.dynamic_slice_in_dim(frozen, i * vp, vp, axis=ax)
                    new, fr = _apply_transforms(transforms, old, new, fr)
                    frozen = jax.lax.dynamic_update_slice_in_dim(
                        frozen, fr, i * vp, ax)
                pr = jax.lax.dynamic_update_slice_in_dim(pr, new, i * vp, ax)
                perr = perr.at[i].set(jnp.max(jnp.abs(new - old)))
                return pr, frozen, perr, nsw + 1

            if thread_level:
                _, _, perr, _ = carry
                return jax.lax.cond(jnp.max(perr) > threshold, do, lambda c: c, carry)
            return do(carry)

        pr, frozen, perr, sweeps = jax.lax.fori_loop(
            0, p, sweep_partition,
            (state.pr, state.frozen, state.perr, state.sweeps)
        )
        return EngineState(pr, frozen, perr, state.it + 1, sweeps)

    return step


def adaptive_schedule(
    sweep: Callable[..., jax.Array],
    *,
    p: int,
    vp: int,
    threshold: float,
    d: float,
    gain: jax.Array,
    prologue: Callable[[jax.Array], Any] | None = None,
) -> Callable:
    """Residual-adaptive No-Sync: the Kollias/Blanco "choose work by
    residual" refinement of :func:`nosync_schedule` (PAPERS.md — asynchronous
    iterative PageRank / delayed asynchronous iteration).

    Two changes over plain No-Sync, both decided **per partition inside the
    schedule** (coarse perforation at partition granularity, not the per-
    vertex Alg-5 transform):

    * **ordering** — partitions are swept in *descending residual-bound*
      order each round (``argsort(-bound)``), so the freshest reads flow from
      the partitions that moved most into the ones that depend on them;
    * **skipping** — a partition whose certified residual bound is at or
      below its fair share of the tolerance — ``threshold / 2``, splitting
      the max-norm budget evenly between the swept partitions' observed
      errors and the skipped partitions' certified drift — is not swept at
      all this round: it sheds the whole sweep, not just the tail of the
      final iteration like ``thread_level``.

    Skipping on the *local observed* error alone converges to a wrong fixed
    point (the nosync docstring's No-Sync-Edge §4.4 phenomenon: a skipped
    partition whose inputs keep moving freezes stale).  What makes the skip
    sound here is a carried certified **bound**, not a stale observation:
    the schedule owns a per-row bound vector (``EngineState.aux``) that is
    reset to the observed delta when a row's partition sweeps and inflated
    by the worst-case influence of every applied update when it skips,

        bound[v] ← [v swept ? 0 : bound[v]] + d · Σ_j gain[v, j] · maxΔ_j ,

    where ``gain[v, j] ≥ Σ_{u∈j, u→v} w_uv/outdeg_u`` is the static
    cross-partition gain operator (see
    ``repro.core.pagerank.vertex_gain_matrix``; callers fold the dangling
    redistribution term in) and ``maxΔ_j`` the max-abs update partition
    ``j`` applied this round.  ``gain`` rows may be per **vertex** (shape
    ``(n_pad, p)`` — tightest, used by the partitioned jax variant) or per
    **partition** (shape ``(p, p)`` with a max over member vertices baked
    in — the Pallas block layout); the partition skip bound is the max of
    its rows' bounds either way.  Since one sweep of a row changes it by at
    most ``d·Σ_j gain[v,j]·‖Δ_j‖_∞``, a partition whose bound is at or
    below the cut genuinely cannot have moved past it — skipping is exact,
    and a partition whose neighbours keep pushing mass at it is re-swept
    the moment its bound crosses the cut.

    The **stop rule is untouched**: ``perr`` is set to the observed delta
    for swept partitions and to the *pre-inflation* bound (≤ cut <
    threshold by construction) for skipped ones, so ``max(perr) ≤
    threshold`` fires exactly when every swept partition observes
    convergence and every skipped one is certified inside its fair share —
    at least as strong a certificate as nosync's, for the same fixed point
    (Lemma 2).  Keeping the *inflated* bound out of ``perr`` is what makes
    this competitive: an earlier design that stopped on the inflated bound
    had to drive the global deltas ``1/(d·‖gain‖)`` below threshold first,
    costing more iterations than it saved sweeps.

    ``sweep``/``prologue`` contracts are exactly :func:`nosync_schedule`'s.
    Transforms are not composed here — partition-level skipping *is* this
    schedule's perforation.  Pass ``aux0=jnp.full((gain.shape[0],), inf)``
    to :func:`solve` (the ``inf`` sentinel makes round one sweep everyone).
    """
    gain = jnp.asarray(gain)
    rows = gain.shape[0]  # n_pad (vertex-granular) or p (partition-granular)

    def partition_bound(bound):
        return bound if rows == p else jnp.max(bound.reshape(p, vp), axis=1)

    def step(state: EngineState) -> EngineState:
        ctx = prologue(state.pr) if prologue is not None else None
        bound = state.aux  # (rows,) certified residual bound, inf at start
        pbound = partition_bound(bound)
        # Skip set fixed at round start: a sweep only lowers its own bound,
        # so in-round recomputation could not activate anyone new.
        cut = jnp.asarray(threshold / 2, pbound.dtype)
        active = pbound > cut
        order = jnp.argsort(-pbound)  # descending residual bound
        deltas0 = jnp.zeros((p,), state.pr.dtype)

        def sweep_position(k, carry):
            i = order[k]

            def do(carry):
                pr, deltas, nsw = carry
                ax = pr.ndim - 1  # partitions live on the last axis
                old = jax.lax.dynamic_slice_in_dim(pr, i * vp, vp, axis=ax)
                new = sweep(i, pr) if prologue is None else sweep(i, pr, ctx)
                pr = jax.lax.dynamic_update_slice_in_dim(pr, new, i * vp, ax)
                delta = jnp.max(jnp.abs(new - old))
                return pr, deltas.at[i].set(delta), nsw + 1

            return jax.lax.cond(active[i], do, lambda c: c, carry)

        pr, deltas, sweeps = jax.lax.fori_loop(
            0, p, sweep_position, (state.pr, deltas0, state.sweeps)
        )
        # Swept rows restart their bound from zero (their residual was just
        # realized as this round's delta); skipped rows keep drifting.  The
        # inf sentinel clears on round one because everyone is active.
        active_rows = active if rows == p else jnp.repeat(active, vp)
        bound = jnp.where(active_rows, jnp.zeros_like(bound), bound)
        bound = bound + jnp.asarray(d, bound.dtype) * (gain @ deltas)
        # Stop-visible error: observed delta when swept, certified
        # PRE-inflation bound (≤ cut) when skipped — never the inflated one.
        perr = jnp.where(active, deltas, pbound)
        return EngineState(pr, state.frozen, perr, state.it + 1, sweeps,
                           bound)

    return step


def freeze_adaptive_schedule(
    sweep: Callable[..., jax.Array],
    *,
    threshold: float,
    d: float,
    gain: jax.Array,
) -> Callable:
    """Residual-adaptive scheduling for sweeps that take a **freeze mask**
    instead of a partition index — the blocked Pallas Gauss–Seidel pass,
    whose tile walk is baked into the kernel grid and cannot be reordered.

    Each unit is one row of the rank layout (a dst block).  Blocks whose
    certified residual bound is at or below the fair-share cut
    (``threshold / 2``) are frozen for the whole pass (the kernel holds
    their ranks, sheds their tiles' update) and unfrozen the moment
    neighbour updates inflate their bound past the cut — the same
    split-bound staleness model as :func:`adaptive_schedule` (carried bound
    in ``aux``, stop-visible ``perr`` holds observed deltas / pre-inflation
    bounds), with ``gain`` at block granularity
    (``partition_gain_matrix``).  The kernel's tile walk is baked into its
    grid, so there is no residual ordering here — skipping is the whole
    play.  ``sweep(pr, frozen)`` must respect the mask exactly
    (``spmv_gs_pass``'s contract: frozen rows keep their input values,
    in-pass fresh reads included).  Pass ``aux0=jnp.full((n_blocks,),
    inf)`` to :func:`solve`.
    """
    gain = jnp.asarray(gain)

    def step(state: EngineState) -> EngineState:
        bound = state.aux  # (n_units,) certified bound, inf at start
        cut = jnp.asarray(threshold / 2, bound.dtype)
        active = bound > cut  # (n_units,) = (rows of pr,)
        frozen_mask = jnp.broadcast_to(
            (~active)[:, None], state.pr.shape).astype(state.pr.dtype)
        new = sweep(state.pr, frozen_mask)
        err = jnp.max(jnp.abs(new - state.pr),
                      axis=tuple(range(1, new.ndim)))
        deltas = jnp.where(active, err, jnp.zeros_like(err))
        new_bound = jnp.where(active, jnp.zeros_like(bound), bound)
        new_bound = new_bound + jnp.asarray(d, bound.dtype) * (gain @ deltas)
        perr = jnp.where(active, err, bound)  # pre-inflation bound ≤ cut
        sweeps = state.sweeps + jnp.sum(active.astype(jnp.int32))
        return EngineState(new, state.frozen, perr, state.it + 1, sweeps,
                           new_bound)

    return step


# ---------------------------------------------------------------------------
# The engine: the one while_loop every variant shares
# ---------------------------------------------------------------------------


def solve(
    step: Callable[[EngineState], EngineState],
    pr0: jax.Array,
    *,
    n_units: int = 1,
    threshold: float,
    max_iter: int,
    track_frozen: bool = False,
    aux0: Any = (),
) -> PageRankResult:
    """Iterate ``step`` until every observed unit error is at or below
    ``threshold`` (or ``max_iter``).  Returns the rank array in the solver's
    own layout — callers strip padding / reshape.

    ``track_frozen`` allocates the perforation freeze mask; leave it off for
    transform-free variants so the while-loop carry holds a zero-size stub
    instead of a full-size boolean array.  ``aux0`` seeds the schedule-owned
    ``EngineState.aux`` slot (the adaptive schedules' carried bound vector);
    the empty-pytree default costs nothing for every other schedule.

    The engine also records the **residual trajectory**: the max observed
    unit error after each iteration, in an ``inf``-padded ``(max_iter,)``
    buffer returned as ``PageRankResult.residuals`` (``inf`` marks rounds
    that never ran; callers slice with ``[:iterations]``).  One f32 scatter
    per iteration — the benchmarks turn this into convergence curves instead
    of endpoint-only records."""
    dtype = pr0.dtype

    def cond(carry):
        state, _ = carry
        return (jnp.max(state.perr) > threshold) & (state.it < max_iter)

    def body(carry):
        state, errs = carry
        new = step(state)
        # state.it is the 0-based index of the iteration `new` just finished
        return new, errs.at[state.it].set(jnp.max(new.perr).astype(jnp.float32))

    init = EngineState(
        pr=pr0,
        frozen=jnp.zeros(pr0.shape if track_frozen else (0,), jnp.bool_),
        perr=jnp.full((n_units,), jnp.inf, dtype),
        it=jnp.asarray(0, jnp.int32),
        sweeps=jnp.asarray(0, jnp.int32),
        aux=aux0,
    )
    errs0 = jnp.full((max_iter,), jnp.inf, jnp.float32)
    final, errs = jax.lax.while_loop(cond, body, (init, errs0))
    return PageRankResult(final.pr, final.it, jnp.max(final.perr), errs,
                          final.sweeps)


# ---------------------------------------------------------------------------
# Variant registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Variant:
    """A registered PageRank variant.

    ``build(g, **opts)`` turns a host :class:`repro.graphs.csr.Graph` into the
    variant's device bundle (opts it does not use are ignored); ``run(bundle,
    d=..., threshold=..., max_iter=..., handle_dangling=..., **opts)`` solves
    and returns a :class:`PageRankResult`.  ``options`` names extra keyword
    options this variant honours beyond the transport set.

    The three metadata fields drive the generic drivers, so a new variant
    shows up in the launcher/benchmarks correctly without touching them:

    * ``layout``  — bundle-layout key: variants with the same ``layout``
      produce identical bundles from identical build opts, so benchmarks
      build once per layout and share it (``"device"``, ``"edge"``,
      ``"identical"``, ``"partitioned"``, ``"blocked"``, ``"distributed"``,
      ``"host"``, and the plan-staged ``"sticd_*"`` layouts — distinct per
      inner variant since the bundle embeds it; empty = private layout,
      never shared).
    * ``backend`` — what executes the sweeps: ``"numpy"`` (host oracle),
      ``"jax"`` (jitted single-device), ``"pallas"`` (Pallas kernels — run
      interpreted on the CPU backend, and benchmarks flag that), ``"shard_map"``
      (device-mesh collectives).
    * ``schedule`` — coordination discipline for the runtime cost model:
      ``"barrier"``, ``"nosync"`` (fresh/stale reads, no global barrier),
      ``"adaptive"`` (nosync clocking + residual-ordered sweeps and
      certified per-unit skipping — see :func:`adaptive_schedule`), or
      ``"sequential"``.
    """

    name: str
    build: Callable[..., Any]
    run: Callable[..., PageRankResult]
    description: str = ""
    options: tuple[str, ...] = ()
    layout: str = ""
    backend: str = "jax"
    schedule: str = "barrier"


_REGISTRY: dict[str, Variant] = {}

# Closed metadata vocabularies the generic drivers dispatch on (see
# :class:`Variant`); ``register_variant`` enforces them at import time and
# ``repro.analysis.contracts`` re-audits the registry against the same sets.
BACKENDS = frozenset({"numpy", "jax", "pallas", "shard_map"})
SCHEDULES = frozenset({"barrier", "nosync", "adaptive", "sequential"})

# Options the launcher/benchmarks pass uniformly; variants that don't need
# one ignore it (e.g. --threads with a barrier variant, --local-sweeps with
# any single-device variant), mirroring the CLI.  ``local_sweeps`` and
# ``send_fraction`` are the mesh-transport knobs of the distributed variants
# (exchange staleness and top-k collective perforation); the coordination
# ``mode`` is baked into the registry name (``distributed_barrier`` vs
# ``distributed_stale``) so it is never a silently-ignored option.
# ``pr0`` is the warm-start vector (an ``(n,)`` float array seeding the
# iteration instead of uniform 1/n): uniquely among transport options it is
# *best-effort by construction* — a warm start can change the iteration
# count but never the fixed point (Lemma 2 again), so a variant that ignores
# it stays correct, merely cold.
_TRANSPORT_OPTS = frozenset(
    {"threads", "block", "tile_cap", "interpret", "local_sweeps",
     "send_fraction", "pr0"}
)


def warm_start_pr(g, prev_pr, *, d: float = DEFAULT_DAMPING,
                  handle_dangling: bool = False) -> np.ndarray:
    """Warm-start seed for :func:`solve_variant` after a graph update: one
    exact float64 sweep of ``g`` applied to the stale fixed point.

    ``prev_pr`` is the converged rank vector of the *pre-update* graph.  One
    power-iteration step through the **new** graph re-normalizes everything a
    structural update perturbs — contributions now divide by the new
    out-degrees, mass routed through deleted edges stops flowing, newly
    dangling vertices stop contributing (or, under ``handle_dangling``, their
    mass is re-spread uniformly) — so the seed already satisfies the new
    sweep's local balance around every changed vertex.  Kollias et al.'s
    asynchronous-iteration analysis (PAPERS.md) is what makes this sound:
    the fixed point is independent of the starting vector, so warm starts
    buy iterations, never correctness.

    Works on any :class:`repro.graphs.csr.Graph`-shaped object (plain
    attribute access; memmap-backed graphs included).
    """
    n = int(g.n)
    prev = np.asarray(prev_pr, dtype=np.float64)
    if prev.shape != (n,):
        raise ValueError(f"prev_pr must have shape ({n},), got {prev.shape}")
    if n == 0:
        return prev.copy()
    out_degree = np.asarray(g.out_degree)
    inv_out = np.where(out_degree > 0, 1.0 / np.maximum(out_degree, 1), 0.0)
    contrib = (prev * inv_out)[np.asarray(g.src)]
    if g.weights is not None:
        contrib = contrib * np.asarray(g.weights)
    acc = np.zeros(n, dtype=np.float64)
    np.add.at(acc, np.asarray(g.dst), contrib)
    base = (1.0 - d) / n
    base_vec = base if g.bias is None else base * np.asarray(g.bias)
    new = base_vec + d * acc
    if handle_dangling:
        new = new + d * prev[out_degree == 0].sum() / n
    return new


def register_variant(name: str, build: Callable, run: Callable,
                     description: str = "",
                     options: tuple[str, ...] = (),
                     layout: str = "",
                     backend: str = "jax",
                     schedule: str = "barrier") -> Variant:
    """Register a PageRank variant under ``name`` and return the record.

    ``build(g, **opts)`` maps a host :class:`repro.graphs.csr.Graph` to the
    variant's device bundle; ``run(bundle, *, d, threshold, max_iter,
    handle_dangling, **opts)`` solves it to a :class:`PageRankResult` whose
    ``pr`` is the **full-length** rank vector (a plan-staged build that
    shrinks the graph must reconstruct before returning — see
    :func:`plan_build` / :func:`plan_run`).  Both callables must tolerate
    the transport options they don't use (accept ``**_``).

    ``description`` is user-facing (``pagerank_run --list`` and the README
    variant table print it verbatim); ``options`` declares extra run options
    beyond the transport set (anything else raises in :func:`build_variant`);
    ``layout``/``backend``/``schedule`` are the metadata triple the generic
    drivers dispatch on — see :class:`Variant` for the vocabulary.  All four
    metadata strings are validated **here**, so a bad registration fails at
    import of its defining module, not first use (the registry test keeps a
    regression copy of the same assertion).

    Registration normally happens at import time of the defining module;
    add new modules to ``_ensure_registered`` so enumeration sees them.
    """
    problems = []
    if not description:
        problems.append("description must be non-empty (printed by --list)")
    if not layout:
        problems.append("layout must be non-empty (bundle-sharing key)")
    if backend not in BACKENDS:
        problems.append(f"backend {backend!r} not in {sorted(BACKENDS)}")
    if schedule not in SCHEDULES:
        problems.append(f"schedule {schedule!r} not in {sorted(SCHEDULES)}")
    if problems:
        raise ValueError(
            f"register_variant({name!r}): " + "; ".join(problems))
    v = Variant(name=name, build=build, run=run, description=description,
                options=options, layout=layout, backend=backend,
                schedule=schedule)
    _REGISTRY[name] = v
    return v


def _ensure_registered() -> None:
    # Variants self-register at import; pull in every module that defines one.
    import repro.core.distributed  # noqa: F401
    import repro.core.pagerank  # noqa: F401
    import repro.kernels.spmv.ops  # noqa: F401
    import repro.ppr.batched  # noqa: F401
    import repro.ppr.push  # noqa: F401


def list_variants() -> tuple[str, ...]:
    _ensure_registered()
    return tuple(sorted(_REGISTRY))


def get_variant(name: str) -> Variant:
    _ensure_registered()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown PageRank variant {name!r}; registered: {list_variants()}"
        ) from None


def build_variant(name: str, g, *, d: float = DEFAULT_DAMPING,
                  **opts) -> tuple[Variant, Any]:
    """Validate ``opts`` and build ``name``'s device bundle from host graph
    ``g``; returns ``(variant, bundle)``.  Callers that need the bundle (the
    launcher records its actual partition count in checkpoints) use this and
    then ``variant.run(bundle, ...)``; everyone else uses
    :func:`solve_variant`.

    ``d`` is forwarded to the build (most builds ignore it): a plan-staged
    build bakes the damping factor into contracted edge weights, so building
    with the ``d`` you intend to run avoids :func:`plan_run`'s re-plan.

    ``g`` may also be a path (``str`` / ``os.PathLike``) to an on-disk graph
    store (:mod:`repro.graphs.store`); it is opened memmap-backed, so builds
    stream the edge arrays instead of loading them resident — the out-of-core
    entry point shared by the launcher's ``--store`` flag and the build
    benchmarks.

    Unknown options raise instead of being silently dropped — a typo'd or
    unsupported option (e.g. ``perforate`` on ``nosync``: use ``nosync_opt``)
    must not let the caller believe it was applied."""
    import os

    if isinstance(g, (str, os.PathLike)):
        from repro.graphs.store import load_graph

        g = load_graph(g, mmap=True)
    v = get_variant(name)
    unknown = set(opts) - _TRANSPORT_OPTS - set(v.options)
    if unknown:
        raise TypeError(
            f"variant {name!r} does not accept option(s) {sorted(unknown)}; "
            f"accepted: {sorted(_TRANSPORT_OPTS | set(v.options))}"
        )
    return v, v.build(g, d=d, **opts)


def bundle_partitions(bundle) -> int:
    """Partition count actually baked into a built bundle — ``p`` for the
    partitioned/distributed layouts, 1 for unpartitioned ones.  Checkpoints
    must record *this*, not the requested ``--threads`` (an unpartitioned
    solve resharded on load as if it had 56 partitions pads the rank vector
    to a layout that was never used)."""
    return int(getattr(bundle, "p", 1))


# ---------------------------------------------------------------------------
# Plan stage: build-time graph decomposition in front of any inner variant
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PlannedBundle:
    """Bundle of a plan-staged variant: the STIC-D decomposition plan plus
    the *inner* variant's bundle built from the plan's core graph.

    ``bundle`` is ``None`` when the plan pruned every vertex (the core is
    empty — e.g. a zero-edge graph is all-dead); :func:`plan_run` then skips
    the inner solve and the reconstruction pass produces the whole vector.

    ``build_opts``/``plan_opts`` record what built this bundle so
    :func:`plan_run` can re-plan when the run-time damping factor differs
    from the one baked into the plan's contracted edge weights.
    """

    plan: Any  # repro.graphs.csr.DecompositionPlan
    inner: Variant
    bundle: Any
    build_opts: dict = dataclasses.field(default_factory=dict)
    plan_opts: dict = dataclasses.field(default_factory=dict)

    @property
    def p(self) -> int:
        # Checkpoints record the layout of the vector they store.  plan_run
        # returns the FULL-LENGTH reconstructed vector, which was never
        # sharded (only the core bundle was), so the checkpoint must say
        # "unpartitioned" — reshard-on-load must not slice the full vector
        # into the core bundle's partition layout.
        return 1


def plan_build(inner: str, **plan_opts) -> Callable:
    """Build-protocol stage: decompose first, build ``inner`` on the core.

    Returns a ``build(g, **opts)`` suitable for :func:`register_variant`:
    it runs :meth:`repro.graphs.csr.DecompositionPlan.from_graph` (with
    ``plan_opts`` — e.g. ``identical=False`` or ``contract=False`` for the
    suffix-only legacy closure) and hands ``plan.core`` to the inner
    variant's build, so partitioning/blocking happens on the shrunken graph
    ("plan first, partition the core second").  The core is weighted when
    chains were contracted mid-graph (per-edge ``d^k`` weights + per-vertex
    teleport bias), which every registered build consumes natively.
    """

    def build(g, **opts):
        from repro.graphs.csr import DecompositionPlan

        # bake the caller's damping factor into the plan (build_variant
        # forwards it) unless the registration pinned one explicitly —
        # plan_run's re-plan then only fires when a bundle built for one d
        # is later run with another
        p_opts = dict(plan_opts)
        p_opts.setdefault("d", opts.get("d", DEFAULT_DAMPING))
        b_opts = {k: val for k, val in opts.items() if k != "d"}
        plan = DecompositionPlan.from_graph(g, **p_opts)
        v = get_variant(inner)
        bundle = v.build(plan.core, **b_opts) if plan.core.n else None
        return PlannedBundle(plan=plan, inner=v, bundle=bundle,
                             build_opts=b_opts, plan_opts=p_opts)

    return build


def plan_run(
    b: PlannedBundle,
    *,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
    pr0=None,
    **opts,
) -> PageRankResult:
    """Run fn of every plan-staged variant: inner solve + reconstruction.

    The inner variant always solves the core with ``handle_dangling=False``
    — dangling redistribution is applied in closed form at reconstruction
    (the redistributed fixed point is a scalar multiple of the plain one —
    L1 normalisation on unweighted graphs, the general
    ``base/(base − (d/n)·Σ_dang pr)`` factor on weighted ones), which keeps
    pruned sinks' mass exact without a feedback loop between the core solve
    and the pruned region.

    Contracted chains bake the damping factor into the core's edge weights
    and bias (``d^k`` per collapsed chain of length ``k``), so a run-time
    ``d`` different from the plan's re-plans and rebuilds the inner bundle
    first — correctness over cache: the stale bundle would silently solve a
    different graph.

    A full-length warm start ``pr0`` is restricted to the core and rescaled
    to the core solve's own ``(1-d)/n_core`` base (the inverse of the
    ``core_pr · n_core / n`` restoration in ``reconstruct``) before being
    handed to the inner variant.
    """
    if b.plan.d_dependent and not np.isclose(d, b.plan.d):
        plan_opts = dict(b.plan_opts)
        plan_opts["d"] = d
        from repro.graphs.csr import DecompositionPlan

        plan = DecompositionPlan.from_graph(b.plan.full, **plan_opts)
        bundle = (b.inner.build(plan.core, **b.build_opts)
                  if plan.core.n else None)
        b = PlannedBundle(plan=plan, inner=b.inner, bundle=bundle,
                          build_opts=b.build_opts, plan_opts=plan_opts)
    if b.bundle is None:  # fully-pruned graph: reconstruction does it all
        it, err, residuals = np.asarray(0, np.int32), np.asarray(0.0), None
        sweeps = None
        core_pr = np.zeros(0, dtype=np.float64)
    else:
        if pr0 is not None:
            core_n = int(b.plan.core.n)
            pr0 = np.asarray(pr0, dtype=np.float64)
            if pr0.shape != (b.plan.n,):
                raise ValueError(
                    f"pr0 must be full-length ({b.plan.n},), got {pr0.shape}")
            opts = dict(opts, pr0=pr0[b.plan.core_index] * (b.plan.n / core_n))
        r = b.inner.run(b.bundle, d=d, threshold=threshold, max_iter=max_iter,
                        handle_dangling=False, **opts)
        it, err, residuals, sweeps = r.iterations, r.err, r.residuals, r.sweeps
        core_pr = np.asarray(r.pr, dtype=np.float64)
    pr = b.plan.reconstruct(core_pr, d=d, handle_dangling=handle_dangling)
    return PageRankResult(pr, it, err, residuals, sweeps)


def plan_stats(bundle) -> dict | None:
    """Decomposition counters of a built bundle (``None`` when unplanned).
    The launcher prints these and ``bench_variants --json`` records them, so
    the preprocessing payoff (core vs full size) is visible, not just wall
    time."""
    if isinstance(bundle, PlannedBundle):
        return bundle.plan.stats()
    return None


def solve_variant(
    name: str,
    g,
    *,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
    **opts,
) -> PageRankResult:
    """Build the bundle for ``name`` and solve — the one-call entry point used
    by the launcher, benchmarks, and the registry round-trip tests."""
    v, bundle = build_variant(name, g, d=d, **opts)
    return v.run(bundle, d=d, threshold=threshold, max_iter=max_iter,
                 handle_dangling=handle_dangling, **opts)
