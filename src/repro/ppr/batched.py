"""Batched multi-seed personalized PageRank (PPR) on the shared engine.

Personalized PageRank replaces the global uniform teleport ``1/n`` with a
per-query teleport distribution ``t`` (uniform over a user's seed vertices):

    pr = (1-d)·t + d·AᵀD⁻¹·pr  [+ d·(dangling mass)·t]

Everything else — sweeps, schedules, transforms, the one ``while_loop`` — is
the global engine with the rank state generalized from ``(n,)`` to ``(b, n)``
(:func:`repro.core.solver.batched_barrier_schedule`): ``b`` independent
queries share one graph bundle, so every existing **build** is reused
unchanged (``ppr_barrier`` shares the ``DeviceGraph`` layout, ``ppr_nosync``
the ``PartitionedGraph`` layout, ``ppr_pallas`` the blocked-COO layout; a
STIC-D plan stage would compose the same way).  Per-row convergence lives in
the engine too: ``perr`` has shape ``(b,)`` and the :func:`row_freeze`
transform exits converged rows early — the primitive under the serving
engine's per-slot early exit.

Dangling mass is redistributed to the row's *own* teleport vector (the mass
a random walk restarts with), which keeps the fixed point linear in ``t``:
with a uniform teleport row every batched variant reproduces the global
``handle_dangling`` fixed point exactly — that linearity is the subsystem's
acceptance test.

Weighted/biased graphs (the STIC-D contraction's representation — see
``repro.graphs.csr.Graph``) are honoured throughout: per-edge weights scale
each contribution inside every batched sweep, and a per-vertex bias scales
the teleport rows themselves (``t_eff = t·bias``), so a uniform-teleport row
on a biased graph reproduces the global biased solve.  Note the dangling
convention difference: PPR re-teleports dangling mass onto the (biased)
teleport row, while the global solvers redistribute it uniformly — the two
fixed points coincide on unbiased graphs only, which is what the round-trip
tests assert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pagerank import DeviceGraph, PartitionedGraph
from repro.core.solver import (
    DEFAULT_DAMPING,
    PageRankResult,
    batched_barrier_schedule,
    nosync_schedule,
    register_variant,
    row_freeze,
    solve,
)
from repro.graphs.csr import Graph
from repro.kernels.spmv.kernel import spmv_gs_pass_multi
from repro.kernels.spmv.ops import PallasGraph

__all__ = [
    "normalize_seeds",
    "teleport_from_seeds",
    "ppr_numpy",
    "ppr_barrier",
    "ppr_nosync",
    "ppr_pallas",
]


def normalize_seeds(seeds) -> tuple[tuple[int, ...], ...]:
    """Canonical batch form of a seeds spec.

    ``None`` → one uniform row; a bare int → one single-seed row; a flat
    sequence of ints → one multi-seed row; a sequence of those → one row
    each.  An empty row ``()`` means "uniform teleport" (a global-PageRank
    query), which is also how the round-trip tests drive the PPR variants.
    """
    if seeds is None:
        return ((),)
    if isinstance(seeds, (int, np.integer)):
        return ((int(seeds),),)
    rows = []
    flat_ints = all(isinstance(s, (int, np.integer)) for s in seeds)
    if flat_ints and len(seeds) > 0:
        return (tuple(int(s) for s in seeds),)
    for row in seeds:
        if isinstance(row, (int, np.integer)):
            rows.append((int(row),))
        else:
            rows.append(tuple(int(s) for s in row))
    return tuple(rows) if rows else ((),)


def teleport_from_seeds(seeds, n: int, n_pad: int | None = None,
                        dtype=np.float64) -> np.ndarray:
    """``(b, n_pad)`` row-stochastic teleport matrix from a seeds spec.

    Each row is uniform over its seed set (empty set → uniform over all
    ``n`` real vertices); padding columns are zero so padded layouts never
    teleport mass onto fake vertices."""
    rows = normalize_seeds(seeds)
    n_pad = n if n_pad is None else n_pad
    t = np.zeros((len(rows), n_pad), dtype=dtype)
    for i, row in enumerate(rows):
        if not row:
            t[i, :n] = 1.0 / max(n, 1)
            continue
        if min(row) < 0 or max(row) >= n:
            raise ValueError(f"seed vertex out of range [0, {n}): {row}")
        # seed SETS: dedup so a repeated seed can't leave the row sub-
        # stochastic (fancy-index assignment would drop the duplicate's
        # mass) — and so (3, 3, 5) and (3, 5) share one fixed point, which
        # is also what the serving engine's warm cache keys on
        row = sorted(set(row))
        t[i, row] = 1.0 / len(row)
    return t


# ---------------------------------------------------------------------------
# Sequential oracle (numpy, float64) — batched Jacobi power iteration
# ---------------------------------------------------------------------------


def ppr_numpy(
    g: Graph,
    teleport: np.ndarray,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-12,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
) -> tuple[np.ndarray, int]:
    """Batched float64 PPR oracle; returns ``(pr (b, n), iterations)``.

    With a uniform teleport row this IS :func:`pagerank_numpy` (teleport
    linearity) — the PPR test tier asserts the round-trip at L1 < 1e-6.
    Per-edge ``g.weights`` scale each contribution; ``g.bias`` scales the
    teleport rows (``t_eff = t·bias``, the convention every device variant
    applies at teleport-build time), so the uniform-row identity extends to
    weighted/biased graphs (without dangling — see the module docstring)."""
    t = np.asarray(teleport, dtype=np.float64)
    b, n = t.shape
    assert n == g.n, f"teleport width {n} != graph n {g.n}"
    if g.bias is not None:
        t = t * g.bias[None, :]
    inv_out = np.where(g.out_degree > 0, 1.0 / np.maximum(g.out_degree, 1), 0.0)
    dang = (g.out_degree == 0).astype(np.float64)
    pr = t.copy()
    rows = np.arange(b)[:, None]
    for it in range(1, max_iter + 1):
        contrib = pr * inv_out[None, :]
        acc = np.zeros((b, n))
        vals = contrib[:, g.src]
        if g.weights is not None:
            vals = vals * g.weights[None, :]
        np.add.at(acc, (rows, g.dst[None, :]), vals)
        new = (1.0 - d) * t + d * acc
        if handle_dangling:
            new += d * (pr @ dang)[:, None] * t
        err = np.abs(new - pr).max()
        pr = new
        if err <= threshold:
            return pr, it
    return pr, max_iter


# ---------------------------------------------------------------------------
# ppr_barrier — batched vertex-centric Jacobi (DeviceGraph layout)
# ---------------------------------------------------------------------------


def make_batched_sweep(src, dst, inv_out, dangling, weights=None, *, n: int,
                       d: float, handle_dangling: bool):
    """``sweep(pr (b,n), tele (b,n)) -> (b,n)`` — one batched Eq.-(1)
    application.  Shared by :func:`ppr_barrier` and the serving engine's
    jitted step (which drives it outside the engine's while_loop).

    ``weights`` (dst-sorted per-edge, or ``None``) scales each contribution;
    a vertex bias is NOT applied here — callers fold it into the teleport
    rows (``t_eff = t·bias``) before the sweep ever runs."""

    def sweep(pr, tele):
        contrib = (pr * inv_out[None, :])[:, src]  # (b, m)
        if weights is not None:
            contrib = contrib * weights[None, :]
        acc = jax.ops.segment_sum(
            contrib.T, dst, num_segments=n, indices_are_sorted=True).T
        new = (1.0 - d) * tele + d * acc
        if handle_dangling:
            dmass = jnp.sum(pr * dangling[None, :], axis=1, keepdims=True)
            new = new + d * dmass * tele
        return new

    return sweep


def bias_scaled(tele: np.ndarray, bias) -> np.ndarray:
    """Fold a per-vertex bias into teleport rows (``t_eff = t·bias``) —
    the ONE place the PPR subsystem applies :attr:`Graph.bias` (the batched
    solvers, the push solver, and the serving engine all route through it),
    so every backend shares the convention.  ``tele`` may be a ``(b, n_pad)``
    matrix or a single ``(n_pad,)`` row; ``bias`` may be shorter than the
    padded teleport width (padding columns carry no bias)."""
    if bias is None:
        return tele
    b = np.asarray(bias, dtype=tele.dtype)
    out = tele.copy()
    out[..., :b.shape[-1]] *= b
    return out


@functools.partial(
    jax.jit, static_argnames=("n", "max_iter", "handle_dangling")
)
def _ppr_barrier_impl(src, dst, inv_out, dangling, weights, tele,
                      *, n, d, threshold, max_iter, handle_dangling):
    sweep = make_batched_sweep(src, dst, inv_out, dangling, weights, n=n, d=d,
                               handle_dangling=handle_dangling)
    b = tele.shape[0]
    step = batched_barrier_schedule(
        lambda pr: sweep(pr, tele), transforms=(row_freeze(threshold),))
    return solve(step, tele, n_units=b, threshold=threshold,
                 max_iter=max_iter, track_frozen=True)


def ppr_barrier(
    dg: DeviceGraph,
    teleport,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    handle_dangling: bool = False,
) -> PageRankResult:
    """Batched multi-seed PPR on the barrier schedule; ``pr`` is ``(b, n)``."""
    tele_np = bias_scaled(np.asarray(teleport, dtype=np.float64), dg.bias)
    tele = jnp.asarray(tele_np, dtype=dg.inv_out.dtype)
    return _ppr_barrier_impl(
        dg.src, dg.dst, dg.inv_out, dg.dangling, dg.weights, tele,
        n=dg.n, d=d, threshold=threshold, max_iter=max_iter,
        handle_dangling=handle_dangling,
    )


# ---------------------------------------------------------------------------
# ppr_nosync — batched partition sweeps, fresh in-iteration reads
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("n", "p", "vp", "n_pad", "max_iter", "thread_level",
                     "handle_dangling"),
)
def _ppr_nosync_impl(
    src_pad, dst_local, emask, inv_out, dangling, tele,
    *, n, p, vp, n_pad, d, threshold, max_iter, thread_level, handle_dangling,
):
    dtype = inv_out.dtype

    def sweep(i, pr, dmass):
        # dmass: (b, 1) per-row dangling snapshot from the prologue.
        # `emask` is the bundle's edge_mult: {0,1} validity on unweighted
        # graphs, per-edge weights (0 on padding) on weighted ones.
        srcs = jax.lax.dynamic_slice_in_dim(src_pad, i, 1, 0)[0]
        dsts = jax.lax.dynamic_slice_in_dim(dst_local, i, 1, 0)[0]
        msk = jax.lax.dynamic_slice_in_dim(emask, i, 1, 0)[0]
        t_i = jax.lax.dynamic_slice_in_dim(tele, i * vp, vp, axis=1)
        contrib = (pr * inv_out[None, :])[:, srcs] * msk[None, :]  # (b, cap)
        acc = jax.ops.segment_sum(
            contrib.T, dsts, num_segments=vp, indices_are_sorted=True).T
        return (1.0 - d) * t_i + d * acc + dmass * t_i

    def dangling_mass(pr):
        if handle_dangling:
            return d * jnp.sum(pr * dangling[None, :], axis=1, keepdims=True)
        return jnp.zeros((pr.shape[0], 1), dtype)

    step = nosync_schedule(sweep, p=p, vp=vp, threshold=threshold,
                           thread_level=thread_level, prologue=dangling_mass)
    r = solve(step, tele, n_units=p, threshold=threshold, max_iter=max_iter)
    return PageRankResult(r.pr[:, :n], r.iterations, r.err, r.residuals,
                          r.sweeps)


def ppr_nosync(
    pg: PartitionedGraph,
    teleport,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    thread_level: bool = True,
    handle_dangling: bool = False,
) -> PageRankResult:
    """Batched PPR on the Alg-3 no-sync schedule (partitions on the last
    axis, each sweep reading every row's freshest ranks)."""
    tele_np = bias_scaled(
        teleport_from_seeds_like(teleport, pg.n, pg.n_pad), pg.bias_pad)
    tele = jnp.asarray(tele_np, pg.inv_out.dtype)
    return _ppr_nosync_impl(
        pg.src_pad, pg.dst_local, pg.edge_mult, pg.inv_out, pg.dangling, tele,
        n=pg.n, p=pg.p, vp=pg.vp, n_pad=pg.n_pad, d=d, threshold=threshold,
        max_iter=max_iter, thread_level=thread_level,
        handle_dangling=handle_dangling,
    )


def teleport_from_seeds_like(teleport, n: int, n_pad: int) -> np.ndarray:
    """Pad an already-built ``(b, n)`` teleport matrix to ``(b, n_pad)``
    (teleport specs that are still seed lists go through
    :func:`teleport_from_seeds` instead)."""
    t = np.asarray(teleport, dtype=np.float64)
    if t.shape[1] == n_pad:
        return t
    assert t.shape[1] == n, (t.shape, n, n_pad)
    out = np.zeros((t.shape[0], n_pad), dtype=t.dtype)
    out[:, :n] = t
    return out


# ---------------------------------------------------------------------------
# ppr_pallas — multi-vector blocked Gauss–Seidel (PallasGraph layout)
# ---------------------------------------------------------------------------


def make_batched_pallas_sweep(
    tiles_src_local, tiles_dst_local, tiles_valid, tile_src_block,
    tile_dst_block, inv_out_blocks, dangling_blocks, tiles_weight=None,
    *, n: int, block: int, d: float, handle_dangling: bool,
    interpret: bool | None = None,
):
    """``sweep(pr_blocks, tele_blocks, frozen_rows (1,b)) -> new blocks`` —
    one batched Gauss–Seidel pass in the kernel's ``(n_blocks, b, block)``
    layout.  The Pallas analogue of :func:`make_batched_sweep`, and the ONE
    home of the PPR base formula ``tele·((1-d) + d·dangling_mass_row)`` on
    this backend — shared by :func:`ppr_pallas` and the serving engine's
    pallas backend so their semantics cannot drift.

    ``tiles_weight`` (``None`` = unweighted: ``tiles_valid`` is reused as
    the kernel's weights operand) scales each edge lane; the teleport rows
    are expected pre-scaled by any vertex bias (:func:`bias_scaled`)."""
    n_blocks = inv_out_blocks.shape[0]
    vmask = (jnp.arange(n_blocks * block) < n).astype(jnp.float32).reshape(
        n_blocks, block)
    wt = tiles_valid if tiles_weight is None else tiles_weight
    d_param = jnp.asarray([[d]], jnp.float32)

    def sweep(pr_blocks, tele_blocks, frozen_rows):
        if handle_dangling:
            dmass = jnp.sum(pr_blocks * dangling_blocks[:, None, :],
                            axis=(0, 2))  # (b,)
        else:
            dmass = jnp.zeros((pr_blocks.shape[1],), jnp.float32)
        base = tele_blocks * (1.0 - d + d * dmass)[None, :, None]
        return spmv_gs_pass_multi(
            pr_blocks, inv_out_blocks, vmask, frozen_rows, base, d_param,
            tiles_src_local, tiles_dst_local, tiles_valid, wt,
            tile_src_block, tile_dst_block, block=block, interpret=interpret,
        )

    return sweep


@functools.partial(
    jax.jit,
    static_argnames=("n", "block", "n_blocks", "max_iter", "handle_dangling",
                     "interpret"),
)
def _ppr_pallas_impl(
    tiles_src_local, tiles_dst_local, tiles_valid, tile_src_block,
    tile_dst_block, inv_out_blocks, dangling_blocks, tiles_weight, tele_blocks,
    *, n, block, n_blocks, d, threshold, max_iter, handle_dangling, interpret,
):
    n_pad = n_blocks * block
    b = tele_blocks.shape[1]
    row_axes = (0, 2)  # batch lives on axis 1 of (n_blocks, b, block)
    psweep = make_batched_pallas_sweep(
        tiles_src_local, tiles_dst_local, tiles_valid, tile_src_block,
        tile_dst_block, inv_out_blocks, dangling_blocks, tiles_weight,
        n=n, block=block, d=d, handle_dangling=handle_dangling,
        interpret=interpret)

    def sweep(pr_blocks, frozen):
        frozen_rows = jnp.max(
            frozen.astype(jnp.float32), axis=row_axes).reshape(1, b)
        return psweep(pr_blocks, tele_blocks, frozen_rows)

    step = batched_barrier_schedule(
        sweep,
        transforms=(row_freeze(threshold, axes=row_axes),),
        pass_frozen=True,
        row_error=lambda new, old: jnp.max(jnp.abs(new - old), axis=row_axes),
    )
    r = solve(step, tele_blocks, n_units=b, threshold=threshold,
              max_iter=max_iter, track_frozen=True)
    pr = r.pr.transpose(1, 0, 2).reshape(b, n_pad)[:, :n]
    return PageRankResult(pr, r.iterations, r.err, r.residuals, r.sweeps)


def blocked_rows(rows: np.ndarray, n_blocks: int, block: int) -> np.ndarray:
    """``(b, n?)`` row matrix → the kernel's ``(n_blocks, b, block)`` layout
    (zero-padded so padding vertices carry no teleport/rank mass)."""
    b = rows.shape[0]
    padded = np.zeros((b, n_blocks * block), dtype=np.float32)
    padded[:, :rows.shape[1]] = rows
    return padded.reshape(b, n_blocks, block).transpose(1, 0, 2)


def ppr_pallas(
    pg: PallasGraph,
    teleport,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    interpret: bool | None = None,
    handle_dangling: bool = False,
) -> PageRankResult:
    """Batched PPR via the multi-vector blocked Gauss–Seidel kernel: all
    ``b`` rank rows VMEM-resident, edge-index streams amortized across the
    batch (``kernels/spmv.spmv_gs_pass_multi``)."""
    t = np.asarray(teleport, dtype=np.float32)
    if pg.n == 0:
        return PageRankResult(jnp.zeros((t.shape[0], 0), jnp.float32),
                              jnp.asarray(0, jnp.int32),
                              jnp.asarray(0.0, jnp.float32))
    if pg.bias_blocks is not None:
        t = bias_scaled(t, np.asarray(pg.bias_blocks).reshape(-1)[:pg.n])
    tele_blocks = jnp.asarray(blocked_rows(t, pg.n_blocks, pg.block))
    return _ppr_pallas_impl(
        pg.tiles_src_local, pg.tiles_dst_local, pg.tiles_valid,
        pg.tile_src_block, pg.tile_dst_block, pg.inv_out_blocks,
        pg.dangling_blocks, pg.tiles_weight, tele_blocks,
        n=pg.n, block=pg.block, n_blocks=pg.n_blocks, d=d,
        threshold=threshold, max_iter=max_iter,
        handle_dangling=handle_dangling, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Registry entries — PPR rides the existing builds
# ---------------------------------------------------------------------------


def _tele(bundle_n: int, seeds, n_pad: int | None = None) -> np.ndarray:
    return teleport_from_seeds(seeds, bundle_n, n_pad=n_pad)


def _ppr_barrier_run(b, *, d=DEFAULT_DAMPING, threshold=1e-8, max_iter=10_000,
                     handle_dangling=False, seeds=None, **_):
    return ppr_barrier(b, _tele(b.n, seeds), d=d, threshold=threshold,
                       max_iter=max_iter, handle_dangling=handle_dangling)


def _ppr_nosync_run(b, *, d=DEFAULT_DAMPING, threshold=1e-8, max_iter=10_000,
                    handle_dangling=False, seeds=None, thread_level=True, **_):
    return ppr_nosync(b, _tele(b.n, seeds, n_pad=b.n_pad), d=d,
                      threshold=threshold, max_iter=max_iter,
                      thread_level=thread_level,
                      handle_dangling=handle_dangling)


def _ppr_pallas_run(b, *, d=DEFAULT_DAMPING, threshold=1e-8, max_iter=10_000,
                    handle_dangling=False, seeds=None, interpret=None, **_):
    return ppr_pallas(b, _tele(b.n, seeds), d=d, threshold=threshold,
                      max_iter=max_iter, interpret=interpret,
                      handle_dangling=handle_dangling)


register_variant(
    "ppr_barrier",
    build=lambda g, **_: DeviceGraph.from_graph(g),
    run=_ppr_barrier_run,
    description="batched multi-seed PPR, vertex-centric Jacobi + per-row freeze",
    options=("seeds",),
    layout="device", backend="jax", schedule="barrier",
)
register_variant(
    "ppr_nosync",
    build=lambda g, threads=56, **_: PartitionedGraph.from_graph(g, p=threads),
    run=_ppr_nosync_run,
    description="batched multi-seed PPR on the Alg-3 fresh-read partition schedule",
    options=("seeds", "thread_level"),
    layout="partitioned", backend="jax", schedule="nosync",
)
register_variant(
    "ppr_pallas",
    build=lambda g, block=256, tile_cap=1024, **_: PallasGraph.build(
        g, block=block, tile_cap=tile_cap),
    run=_ppr_pallas_run,
    description="batched multi-seed PPR, multi-vector blocked GS kernel (VMEM-resident rows)",
    options=("seeds",),
    layout="blocked", backend="pallas", schedule="nosync",
)
