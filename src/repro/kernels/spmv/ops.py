"""Jitted public wrappers around the blocked-SpMV Pallas kernels.

Two schedules share the one convergence engine (:mod:`repro.core.solver`):

* ``schedule="barrier"`` — Jacobi: one :func:`spmv_blocked` sweep per
  iteration against the previous iterate.
* ``schedule="nosync"`` — the paper's Alg-3 schedule on the blocked kernel:
  one :func:`spmv_gs_pass` per iteration sweeps dst blocks in order, each
  tile gathering from the freshest rank blocks (Lemma 2: same fixed point,
  Fig 7: no more iterations than barrier).

Both support ``handle_dangling``; the dangling mass is refreshed from the
current ranks at the top of each pass, which leaves the fixed point
unchanged.

``pallas_nosync_opt`` adds Alg-5 loop perforation to the nosync schedule:
the engine's ``perforation`` transform owns the freeze mask, and the kernel
receives it as an extra VMEM operand so in-pass fresh reads see frozen
vertices at their frozen values.

``schedule="adaptive"`` reuses the same freeze-mask operand for
residual-adaptive **block skipping**: dst blocks whose certified residual
bound sits at or below the fair-share cut are frozen for the whole pass
(:func:`repro.core.solver.freeze_adaptive_schedule`), driven by the
``(n_blocks, n_blocks)`` gain certificate the build computes on request
(``gain=True`` — dense in block count, so only the ``pallas_adaptive``
registration pays for it).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solver import (
    DEFAULT_DAMPING,
    PageRankResult,
    barrier_schedule,
    freeze_adaptive_schedule,
    perforation,
    register_variant,
    solve,
)
from repro.graphs.csr import (
    Graph,
    block_pair_counts,
    build_blocked_coo,
    inv_out_and_dangling,
    tiles_from_counts,
)
from repro.kernels.spmv.kernel import (
    MAX_TILES,
    VMEM_CAPACITY,
    multi_pass_vmem_bytes,
    spmv_blocked,
    spmv_gs_pass,
)
from repro.utils.tracing import LAYOUTS

SCHEDULES = ("barrier", "nosync", "adaptive")

# Device time of one grid step of spmv_gs_pass_multi on a TPU v5 lite:
# STEP_US + STEP_US_PER_LANE · block · tile_cap, least squares over 12
# lane-dense layouts (blocks 256–4096, caps 128–1024) of the Graph500
# scale-16 graph at 8 rows, worst residual 3.2%; spmv_gs_pass at one row
# costs 0.1-7% more (scripts/tile_step_cost.py; PERF.md, section 6).  Fit
# when the tile contraction ran f32 one-hots at HIGHEST precision; the bf16
# split's steps fit 0.220 + 1.0e-6 · block · tile_cap (docs/KERNELS.md)
STEP_US = 0.128
STEP_US_PER_LANE = 6.0e-6
# the layouts choose_layout weighs: blocks of whole 128-lane rows, and caps
# a multiple of 128 (a narrower cap pays for 128 lanes: PERF.md, section 6)
_BLOCKS = range(128, 4096 + 1, 128)
_CAPS = (128, 256, 512, 1024)


def step_us(block: int, tile_cap: int) -> float:
    """Predicted device time of one grid step at a layout (µs)."""
    return STEP_US + STEP_US_PER_LANE * block * tile_cap


def choose_layout(g: Graph, rows: int = 8) -> tuple[int, int]:
    """The ``(block, tile_cap)`` whose sweep the cost model predicts
    fastest: least ``tiles(block, cap) × step_us(block, cap)`` over blocks a
    multiple of 128 up to 4096 and caps in 128–1024, counted from the
    graph's ``(dst_block, src_block)`` histogram without building tiles.

    A grid step costs what its ``(block, cap)`` one-hot costs whether its
    lanes hold edges or padding, so the layout that wins is the one whose
    tiles are well filled at the smallest area.  Only layouts one kernel
    call takes are weighed: at most ``MAX_TILES`` tiles (the SMEM tile
    maps) and the VMEM of :func:`~repro.kernels.spmv.kernel.spmv_gs_pass_multi`
    at ``rows`` rows within a core's.  A function of the graph and ``rows``
    alone; ties go to the smaller block, then the smaller cap."""
    best_cost, best = float("inf"), None
    for block in _BLOCKS:
        n_blocks = -(-g.n // block)
        caps = [c for c in _CAPS if multi_pass_vmem_bytes(
            n_blocks, block, c, rows) <= VMEM_CAPACITY]
        # a layout makes at least one tile per dst block and per cap edges
        floor = {c: max(n_blocks, -(-g.m // c)) for c in caps}
        caps = [c for c in caps if floor[c] <= MAX_TILES
                and floor[c] * step_us(block, c) < best_cost]
        if caps:
            keys, counts = block_pair_counts(g, block)
            for cap in caps:
                tiles = tiles_from_counts(keys, counts, n_blocks, cap)
                cost = tiles * step_us(block, cap)
                if tiles <= MAX_TILES and cost < best_cost:
                    best_cost, best = cost, (block, cap)
        if n_blocks == 1:  # larger blocks only add padding
            break
    if best is None:
        raise ValueError(
            f"no tile layout of a {g.n:,}-vertex, {g.m:,}-edge graph fits "
            f"one kernel call ({MAX_TILES:,} tiles, {VMEM_CAPACITY >> 20} "
            f"MiB of VMEM at {rows} rows); shard the vertex space first")
    return best


class TileLayout(NamedTuple):
    """The tile layout a :class:`PallasGraph` was built with."""

    block: int
    tile_cap: int
    tiles: int
    fill: float  # edges / (tiles · tile_cap): one-hot lanes that hold an edge
    chosen: bool = False  # picked by choose_layout, not passed


class PallasGraph(NamedTuple):
    """Device-side bundle for the Pallas PageRank path.

    ``tiles_weight``/``bias_blocks`` are ``None`` on unweighted/unbiased
    graphs — the sweeps then hand the kernels ``tiles_valid``/``vmask`` in
    their place (same buffers, so the fast path streams no extra bytes)."""

    n: int
    block: int
    n_blocks: int
    tiles_src_local: jax.Array
    tiles_dst_local: jax.Array
    tiles_valid: jax.Array
    tile_src_block: jax.Array
    tile_dst_block: jax.Array
    inv_out_blocks: jax.Array  # (n_blocks, block)
    dangling_blocks: jax.Array  # (n_blocks, block) — outdeg==0 mask, padded 0
    tiles_weight: jax.Array | None = None  # (T, cap) per-edge weights
    bias_blocks: jax.Array | None = None  # (n_blocks, block) base multiplier
    gain: jax.Array | None = None  # (n_blocks, n_blocks) cross-block gain
    layout: TileLayout | None = None

    @classmethod
    def build(cls, g: Graph, block: int = 256, tile_cap: int = 1024,
              gain: bool = False) -> "PallasGraph":
        b = build_blocked_coo(g, block=block, tile_cap=tile_cap)
        n_pad = b.n_blocks * block
        inv, dang = inv_out_and_dangling(g.out_degree, n_pad)
        inv = inv.astype(np.float32)
        dang = dang.astype(np.float32)
        bias_blocks = None
        if g.bias is not None:
            bias = np.zeros(n_pad, dtype=np.float32)
            bias[:g.n] = g.bias
            bias_blocks = jnp.asarray(bias.reshape(b.n_blocks, block))
        gain_mat = None
        if gain:
            # dense (n_blocks, n_blocks) — quadratic in block count, so the
            # certificate is opt-in rather than a tax on every blocked build
            from repro.core.pagerank import partition_gain_matrix

            gain_mat = jnp.asarray(
                partition_gain_matrix(g, block, b.n_blocks), jnp.float32)
        return cls(
            n=g.n,
            block=block,
            n_blocks=b.n_blocks,
            tiles_src_local=jnp.asarray(b.tiles_src_local),
            tiles_dst_local=jnp.asarray(b.tiles_dst_local),
            tiles_valid=jnp.asarray(b.tiles_valid),
            tile_src_block=jnp.asarray(b.tile_src_block),
            tile_dst_block=jnp.asarray(b.tile_dst_block),
            inv_out_blocks=jnp.asarray(inv.reshape(b.n_blocks, block)),
            dangling_blocks=jnp.asarray(dang.reshape(b.n_blocks, block)),
            tiles_weight=(None if b.tiles_weight is None
                          else jnp.asarray(b.tiles_weight)),
            bias_blocks=bias_blocks,
            gain=gain_mat,
            layout=TileLayout(block, tile_cap, b.num_tiles,
                              g.m / max(1, b.num_tiles * tile_cap)),
        )


@functools.partial(
    jax.jit,
    static_argnames=("n", "block", "n_blocks", "max_iter", "schedule",
                     "handle_dangling", "interpret", "perforate"),
)
def _pallas_impl(
    tiles_src_local, tiles_dst_local, tiles_valid, tile_src_block,
    tile_dst_block, inv_out_blocks, dangling_blocks, tiles_weight, bias_blocks,
    gain, warm,
    *, n, block, n_blocks, d, threshold, max_iter, schedule, handle_dangling,
    interpret, perforate,
):
    n_pad = n_blocks * block
    base = (1.0 - d) / n
    # padding vertices have no in-edges: keep their rank at 0 via a mask
    vmask = (jnp.arange(n_pad) < n).astype(jnp.float32).reshape(n_blocks, block)
    # unweighted/unbiased fast path: reuse the already-resident operands
    # (validity doubles as weight: val·val = val; vmask doubles as bias)
    wt = tiles_valid if tiles_weight is None else tiles_weight
    bz = vmask if bias_blocks is None else bias_blocks

    def dangling_mass(pr):
        if not handle_dangling:
            return jnp.asarray(0.0, jnp.float32)
        return jnp.sum(pr * dangling_blocks) / n

    if schedule == "barrier":

        def sweep(pr):
            contrib = pr * inv_out_blocks
            # the weights operand rides in the valid slot: spmv_blocked's
            # tile math multiplies one (cap,) factor per lane either way
            acc = spmv_blocked(
                contrib, tiles_src_local, tiles_dst_local, wt,
                tile_src_block, tile_dst_block, block=block, interpret=interpret,
            )
            return (base * bz + d * acc + d * dangling_mass(pr)) * vmask

    else:  # nosync/adaptive: one blocked Gauss–Seidel pass per iteration

        def sweep(pr, frozen=None):
            params = jnp.stack(
                [jnp.asarray(base, jnp.float32),
                 jnp.asarray(d, jnp.float32),
                 jnp.asarray(d * dangling_mass(pr), jnp.float32)]
            ).reshape(1, 3)
            # freeze mask as an extra VMEM operand: frozen vertices hold
            # their rank through the pass, so in-pass fresh reads stay
            # consistent with the engine transform's post-pass revert
            frz = (jnp.zeros_like(vmask) if frozen is None
                   else frozen.astype(jnp.float32))
            return spmv_gs_pass(
                pr, inv_out_blocks, vmask, bz, frz, params,
                tiles_src_local, tiles_dst_local, tiles_valid, wt,
                tile_src_block, tile_dst_block, block=block, interpret=interpret,
            )

    # warm start rides in blocked layout, already vmask-ed by the wrapper
    pr0 = (jnp.full((n_blocks, block), 1.0 / n, jnp.float32) * vmask
           if warm is None else warm)
    if schedule == "adaptive":
        # block-level residual-adaptive skipping: the freeze mask that Alg-5
        # perforation feeds per-vertex is driven per dst block here, from the
        # certified (n_blocks, n_blocks) gain bound (one engine unit = one
        # block row, so the stop rule sees per-block observed deltas)
        gain_eff = gain
        if handle_dangling:
            dang_counts = jnp.sum(dangling_blocks, axis=1)
            gain_eff = gain + (dang_counts / n)[None, :]
        step = freeze_adaptive_schedule(
            sweep, threshold=threshold, d=d, gain=gain_eff)
        r = solve(step, pr0, n_units=n_blocks, threshold=threshold,
                  max_iter=max_iter,
                  aux0=jnp.full((n_blocks,), jnp.inf, jnp.float32))
        return PageRankResult(r.pr.reshape(-1)[:n], r.iterations, r.err,
                              r.residuals, r.sweeps)
    # Perforation is the ENGINE's transform (Alg 5), not a kernel fork: the
    # kernel only respects the mask the transform maintains.
    transforms = (perforation(threshold),) if perforate else ()
    step = barrier_schedule(sweep, transforms, pass_frozen=perforate)
    r = solve(step, pr0, threshold=threshold, max_iter=max_iter,
              track_frozen=perforate)
    return PageRankResult(r.pr.reshape(-1)[:n], r.iterations, r.err,
                          r.residuals, r.sweeps)


def pagerank_pallas(
    pg: PallasGraph,
    d: float = DEFAULT_DAMPING,
    threshold: float = 1e-8,
    max_iter: int = 10_000,
    interpret: bool | None = None,
    schedule: str = "barrier",
    handle_dangling: bool = False,
    perforate: bool = False,
    pr0=None,
) -> PageRankResult:
    """Full Pallas-kernel PageRank on the chosen schedule.  ``pr0`` warm-
    starts the iteration from a full-length ``(n,)`` host vector (reshaped
    into the blocked layout; padding lanes zeroed) — same fixed point,
    fewer sweeps after a small graph update."""
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, got {schedule!r}")
    if perforate and schedule != "nosync":
        raise ValueError("perforate requires the nosync schedule "
                         "(the freeze mask is a spmv_gs_pass operand; the "
                         "adaptive schedule owns the mask itself)")
    if schedule == "adaptive" and pg.gain is None:
        raise ValueError(
            "adaptive schedule needs the block gain certificate — rebuild "
            "with PallasGraph.build(g, gain=True)")
    if pg.n == 0:
        return PageRankResult(jnp.zeros((0,), jnp.float32),
                              jnp.asarray(0, jnp.int32),
                              jnp.asarray(0.0, jnp.float32))
    if schedule != "barrier" and pg.layout is not None:
        LAYOUTS["spmv_gs_pass"] = pg.layout._asdict()
    warm = None
    if pr0 is not None:
        padded = np.zeros(pg.n_blocks * pg.block, dtype=np.float32)
        padded[:pg.n] = np.asarray(pr0)
        warm = jnp.asarray(padded.reshape(pg.n_blocks, pg.block))
    return _pallas_impl(
        pg.tiles_src_local, pg.tiles_dst_local, pg.tiles_valid,
        pg.tile_src_block, pg.tile_dst_block, pg.inv_out_blocks,
        pg.dangling_blocks, pg.tiles_weight, pg.bias_blocks, pg.gain, warm,
        n=pg.n, block=pg.block, n_blocks=pg.n_blocks,
        d=d, threshold=threshold, max_iter=max_iter, schedule=schedule,
        handle_dangling=handle_dangling, interpret=interpret,
        perforate=perforate,
    )


# ---------------------------------------------------------------------------
# Registry entries
# ---------------------------------------------------------------------------


def _build(g, block: int = 256, tile_cap: int = 1024, gain: bool = False, **_):
    return PallasGraph.build(g, block=block, tile_cap=tile_cap, gain=gain)


def _run(schedule, perforate=False):
    def run(b, *, d=DEFAULT_DAMPING, threshold=1e-8, max_iter=10_000,
            handle_dangling=False, interpret=None, pr0=None, **_):
        return pagerank_pallas(
            b, d=d, threshold=threshold, max_iter=max_iter, interpret=interpret,
            schedule=schedule, handle_dangling=handle_dangling,
            perforate=perforate, pr0=pr0,
        )

    return run


register_variant(
    "pallas", build=_build, run=_run("barrier"),
    description="blocked MXU SpMV kernel, Jacobi (barrier) schedule",
    layout="blocked", backend="pallas", schedule="barrier",
)
register_variant(
    "pallas_nosync", build=_build, run=_run("nosync"),
    description="blocked MXU SpMV kernel, Alg-3 fresh-read (Gauss–Seidel) schedule",
    layout="blocked", backend="pallas", schedule="nosync",
)
register_variant(
    "pallas_nosync_opt", build=_build, run=_run("nosync", perforate=True),
    description="blocked MXU SpMV kernel, Alg-3 fresh-read schedule + Alg-5 perforation",
    layout="blocked", backend="pallas", schedule="nosync",
)
register_variant(
    "pallas_adaptive",
    # private layout on purpose: the "blocked" bundle benchmarks share lacks
    # the gain certificate this schedule requires
    build=functools.partial(_build, gain=True), run=_run("adaptive"),
    description="blocked MXU SpMV kernel, residual-adaptive certified block skipping",
    layout="blocked_gain", backend="pallas", schedule="adaptive",
)
