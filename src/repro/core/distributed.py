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
unchanged) and folded into every sweep's base term.

The solvers are also **registry entries** (``distributed_barrier``,
``distributed_stale``, ``distributed_topk``): ``build`` makes a
:class:`DistributedBundle` (PartitionedGraph + 1-D mesh over however many
devices exist, capped by ``threads``), so the launcher, benchmarks, and the
Lemma-2 round-trip tests cover the pod-scale modes exactly like the
single-device variants.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.core.pagerank import DEFAULT_DAMPING, PageRankResult, PartitionedGraph
from repro.core.solver import register_variant


def _sweep(pr_full, local, srcs, dsts, emask, inv_out, base, d, vp, offset):
    """One Gauss–Seidel sweep of the local partition against pr_full.

    ``base`` is the per-vertex additive term — scalar ``(1-d)/n`` (or the
    ``(vp,)`` bias-scaled vector on biased graphs) plus, when dangling mass
    is handled, this round's redistributed d·(dangling mass)/n.  ``emask``
    is the bundle's effective per-edge multiplier ({0,1} validity on
    unweighted graphs, the per-edge weights on weighted ones — see
    ``PartitionedGraph.edge_mult``)."""
    pr_full = jax.lax.dynamic_update_slice_in_dim(pr_full, local, offset, 0)
    contrib = (pr_full * inv_out)[srcs] * emask
    acc = jax.ops.segment_sum(contrib, dsts, num_segments=vp, indices_are_sorted=True)
    new = base + d * acc
    err = jnp.max(jnp.abs(new - local))
    return new, err


def distributed_pagerank(
    pg: PartitionedGraph,
    mesh: Mesh,
    axis: str = "data",
    mode: str = "barrier",
    local_sweeps: int = 4,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_rounds: int = 10_000,
    shard_level_convergence: bool = False,
    handle_dangling: bool = False,
) -> PageRankResult:
    """Run PageRank on ``mesh`` with partitions sharded along ``axis``.

    Returns (pr[:n], rounds, err). ``rounds`` counts *global exchanges* —
    the paper's Fig-7 "iterations" comparison maps to rounds×sweeps for
    compute and rounds for synchronization.
    """
    if mode not in ("barrier", "stale"):
        raise ValueError(f"unknown mode {mode!r}")
    p = pg.p
    if p != mesh.shape[axis]:
        raise ValueError(f"graph partitions ({p}) != mesh axis size ({mesh.shape[axis]})")
    vp, n, n_pad = pg.vp, pg.n, pg.n_pad
    k = local_sweeps if mode == "stale" else 1
    dtype = pg.inv_out.dtype
    base = jnp.asarray((1.0 - d) / n, dtype)
    thr = jnp.asarray(threshold, dtype)

    def solver(src_pad, dst_local, emask, inv_out, dangling, *rest):
        # shapes inside shard_map: src_pad (1, cap), inv_out (n_pad,)
        # replicated; rest = (bias_pad,) on biased graphs, () otherwise
        srcs, dsts, msk = src_pad[0], dst_local[0], emask[0]
        idx = jax.lax.axis_index(axis)
        offset = idx * vp
        base_local = base if not rest else base * jax.lax.dynamic_slice_in_dim(
            rest[0], offset, vp, 0)
        local0 = jnp.full((vp,), 1.0 / n, dtype)

        def round_body(state):
            local, err_local, _, rounds = state
            # exchange: gather the full rank vector (the barrier / halo snapshot)
            pr_full = jax.lax.all_gather(local, axis, tiled=True)
            # dangling-mass snapshot at round start (iteration-start semantics,
            # one O(n) reduction per exchange; padding slots have dangling=0)
            base_eff = base_local + (d * jnp.sum(pr_full * dangling) / n
                                     if handle_dangling else 0.0)

            def do_sweeps(local):
                # Convergence metric = FIRST sweep's residual (fresh-halo
                # Jacobi residual). Later sweeps iterate against the same
                # snapshot, so their shrinking residual reflects only local
                # convergence and would exit prematurely.
                def one(i, carry):
                    local, err = carry
                    new, err_s = _sweep(pr_full, local, srcs, dsts, msk, inv_out, base_eff, d, vp, offset)
                    err = jnp.where(i == 0, err_s, err)
                    return new, err

                return jax.lax.fori_loop(0, k, one, (local, err_local))

            if shard_level_convergence:
                # CAUTION: skipping on the shard's own residual can freeze a
                # shard whose inputs change later (the paper's No-Sync-Edge
                # §4.4 failure mode, caught by the property tests) — and in
                # lockstep SPMD it saves no wall-clock anyway. Off by default.
                local, err_local = jax.lax.cond(
                    err_local > thr, do_sweeps, lambda l: (l, err_local), local
                )
            else:
                local, err_local = do_sweeps(local)
            err_global = jax.lax.pmax(err_local, axis)
            return local, err_local, err_global, rounds + 1

        def round_cond(state):
            _, _, err_global, rounds = state
            return (err_global > thr) & (rounds < max_rounds)

        init = (local0, jnp.asarray(jnp.inf, dtype), jnp.asarray(jnp.inf, dtype), jnp.asarray(0, jnp.int32))
        local, _, err_global, rounds = jax.lax.while_loop(round_cond, round_body, init)
        return local, err_global[None], rounds[None]

    # weights ride in the emask slot (PartitionedGraph.edge_mult — already
    # partitioned alongside the edges); the bias vector is one extra
    # replicated operand, present only on biased graphs
    extra = () if pg.bias_pad is None else (pg.bias_pad,)
    mapped = jax.shard_map(
        solver,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P(), P())
        + (P(),) * len(extra),
        out_specs=(P(axis), P(axis), P(axis)),
        check_vma=False,
    )

    # Note: stale-mode GS sweeps inside one round reuse the *same* snapshot
    # for remote ranks; pr_full is refreshed with fresh local ranks each sweep.
    pr, errs, rounds = jax.jit(mapped)(pg.src_pad, pg.dst_local, pg.edge_mult,
                                       pg.inv_out, pg.dangling, *extra)
    return PageRankResult(pr[:n], rounds[0], jnp.max(errs))


def distributed_pagerank_topk(
    pg: PartitionedGraph,
    mesh: Mesh,
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
    p, vp, n, n_pad = pg.p, pg.vp, pg.n, pg.n_pad
    if p != mesh.shape[axis]:
        raise ValueError("partitions != mesh axis size")
    k = max(1, int(vp * send_fraction))
    dtype = pg.inv_out.dtype
    base = jnp.asarray((1.0 - d) / n, dtype)
    thr = jnp.asarray(threshold, dtype)

    def solver(src_pad, dst_local, emask, inv_out, dangling, *rest):
        srcs, dsts, msk = src_pad[0], dst_local[0], emask[0]
        idx_range = jax.lax.axis_index(axis)
        offset = idx_range * vp
        base_local = base if not rest else base * jax.lax.dynamic_slice_in_dim(
            rest[0], offset, vp, 0)
        local0 = jnp.full((vp,), 1.0 / n, dtype)
        snap0 = jnp.full((n_pad,), 1.0 / n, dtype)
        sent0 = jnp.full((vp,), 1.0 / n, dtype)

        def round_body(state):
            local, snap, sent, err_local, _, rounds = state
            # 1. communication perforation: publish top-k deltas only
            delta = local - sent
            _, top_idx = jax.lax.top_k(jnp.abs(delta), k)
            top_val = local[top_idx]
            sent = sent.at[top_idx].set(top_val)
            g_idx = jax.lax.all_gather(top_idx + offset, axis)  # (p,k)
            g_val = jax.lax.all_gather(top_val, axis)  # (p,k)
            snap = snap.at[g_idx.reshape(-1)].set(g_val.reshape(-1))

            # dangling-mass snapshot from the freshest local view (snapshot
            # with own fresh ranks folded in) — bounded staleness, fixed
            # point unchanged (Lemma 2)
            if handle_dangling:
                pr_eff = jax.lax.dynamic_update_slice_in_dim(snap, local, offset, 0)
                base_eff = base_local + d * jnp.sum(pr_eff * dangling) / n
            else:
                base_eff = base_local

            # 2. local Gauss–Seidel sweeps against the snapshot
            def one(i, carry):
                loc, err = carry
                new, err_s = _sweep(snap, loc, srcs, dsts, msk, inv_out, base_eff, d, vp, offset)
                err = jnp.where(i == 0, err_s, err)
                return new, err

            local, err_local = jax.lax.fori_loop(0, local_sweeps, one, (local, err_local))
            # residual must also cover unpublished deltas (ledger drain)
            resid = jnp.maximum(err_local, jnp.max(jnp.abs(local - sent)))
            err_global = jax.lax.pmax(resid, axis)
            return local, snap, sent, err_local, err_global, rounds + 1

        def cond(state):
            *_, err_global, rounds = state
            return (err_global > thr) & (rounds < max_rounds)

        init = (local0, snap0, sent0, jnp.asarray(jnp.inf, dtype),
                jnp.asarray(jnp.inf, dtype), jnp.asarray(0, jnp.int32))
        local, _, _, _, err_global, rounds = jax.lax.while_loop(cond, round_body, init)
        return local, err_global[None], rounds[None]

    extra = () if pg.bias_pad is None else (pg.bias_pad,)
    mapped = jax.shard_map(
        solver,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P(), P())
        + (P(),) * len(extra),
        out_specs=(P(axis), P(axis), P(axis)),
        check_vma=False,
    )
    pr, errs, rounds = jax.jit(mapped)(pg.src_pad, pg.dst_local, pg.edge_mult,
                                       pg.inv_out, pg.dangling, *extra)
    return PageRankResult(pr[:n], rounds[0], jnp.max(errs))


# ---------------------------------------------------------------------------
# Registry entries — DistributedBundle build + the three pod-scale modes
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DistributedBundle:
    """Device bundle of the distributed variants: the partitioned graph plus
    the 1-D mesh its partitions are sharded over."""

    pg: PartitionedGraph
    mesh: Mesh
    axis: str = "data"

    @property
    def p(self) -> int:
        return self.pg.p


def solver_mesh(p: Optional[int] = None, axis: str = "data") -> Mesh:
    """1-D mesh for the distributed solvers: ``min(p, devices)`` shards (all
    devices when ``p`` is None).  The partition count must equal the mesh
    axis size, so the build fn derives ``p`` from this mesh — asking for 56
    partitions on a single-host run degrades gracefully instead of raising."""
    n_dev = jax.device_count()
    p = n_dev if p is None else max(1, min(int(p), n_dev))
    return jax.make_mesh((p,), (axis,), axis_types=(AxisType.Auto,))


def _dist_build(g, threads: int = 8, **_) -> DistributedBundle:
    mesh = solver_mesh(threads)
    axis = "data"
    return DistributedBundle(
        pg=PartitionedGraph.from_graph(g, p=mesh.shape[axis]), mesh=mesh,
        axis=axis,
    )


def _dist_run(mode: str):
    def run(b: DistributedBundle, *, d=DEFAULT_DAMPING, threshold=1e-8,
            max_iter=10_000, handle_dangling=False, local_sweeps=4, **_):
        return distributed_pagerank(
            b.pg, b.mesh, axis=b.axis, mode=mode, local_sweeps=local_sweeps,
            d=d, threshold=threshold, max_rounds=max_iter,
            handle_dangling=handle_dangling,
        )

    return run


def _dist_topk_run(b: DistributedBundle, *, d=DEFAULT_DAMPING, threshold=1e-8,
                   max_iter=10_000, handle_dangling=False, local_sweeps=2,
                   send_fraction=0.125, **_):
    return distributed_pagerank_topk(
        b.pg, b.mesh, axis=b.axis, send_fraction=send_fraction,
        local_sweeps=local_sweeps, d=d, threshold=threshold,
        max_rounds=max_iter, handle_dangling=handle_dangling,
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
