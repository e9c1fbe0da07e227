"""The Gauss–Seidel kernels against a sweep-level reference.

``spmv_gs_pass_ref`` is one sweep in float64 from the graph's edges: dst
blocks commit in order, sources in lower blocks are read fresh, the rest
(the block's own included) from the sweep's input.  Each kernel runs in
interpret mode on graphs spanning at least three dst blocks, where the
fresh-read order changes the answer by far more than the tolerance
(``test_sweep_reference_differs_from_jacobi``), so a kernel or layout that
read stale ranks across blocks, or skipped a block's commit, fails here.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.solver import solve_variant
from repro.graphs import DecompositionPlan, make_dataset, rmat_graph
from repro.graphs.csr import Graph
from repro.kernels.spmv import (
    PallasGraph,
    spmv_gs_pass,
    spmv_gs_pass_multi,
    spmv_gs_pass_ref,
)

from test_decomposition import chain_sink_heavy_graph

D = 0.85
EPS = float(np.finfo(np.float32).eps)
# A sweep's error in f32 ulps of the largest rank: 4 for the tile scatter
# (tests/test_kernels.py::test_tile_contraction_keeps_f32), ~2 for the f32
# 1/outdeg, weight and their products, ~2 for the commit's base + d·acc,
# doubled for the fresh reads, which carry the lower blocks' committed error.
SWEEP_ULPS = 16
LAYOUTS = ((128, 128), (256, 128), (640, 128))
GRAPHS = ("kron_repeated", "kron12", "rmat_skew", "chains", "dangling",
          "weighted_core")


def _dangling_graph() -> Graph:
    """Kronecker edges with 45% of vertices stripped of out-edges and no
    in-edge into vertices 640-1279: a dst block with no in-edge at every
    layout (the kernel must still commit it, as teleport and dangling
    mass)."""
    g = rmat_graph(11, avg_degree=8, seed=4, dedupe=False)
    rng = np.random.default_rng(4)
    sinks = rng.random(g.n) < 0.45
    keep = ~sinks[g.src] & ~((g.dst >= 640) & (g.dst < 1280))
    return Graph.from_edges(g.n, g.src[keep], g.dst[keep])


def _weighted_core() -> Graph:
    """The weighted core of a Kronecker graph with 150 five-vertex chains
    between its vertices: the chains contract into edges weighted by powers
    of ``d`` and fold their teleport into the core's bias."""
    g = rmat_graph(12, avg_degree=8, seed=6)
    rng = np.random.default_rng(6)
    src, dst, n = [g.src], [g.dst], g.n
    for _ in range(150):
        path = [int(rng.integers(0, g.n))] + list(range(n, n + 5)) \
            + [int(rng.integers(0, g.n))]
        n += 5
        src.append(np.asarray(path[:-1]))
        dst.append(np.asarray(path[1:]))
    full = Graph.from_edges(n, np.concatenate(src), np.concatenate(dst))
    return DecompositionPlan.from_graph(full, d=D).core


@functools.cache
def graph(name: str) -> Graph:
    if name == "kron_repeated":  # Kronecker edges, duplicates kept
        return rmat_graph(11, avg_degree=8, seed=1, dedupe=False)
    if name == "kron12":
        return rmat_graph(12, avg_degree=8, seed=2)
    if name == "rmat_skew":
        return make_dataset("rmatSkew", scale_down=64, seed=3)
    if name == "chains":
        return chain_sink_heavy_graph(n_core=600, chain_len=1000,
                                      n_sinks=400, seed=5)
    if name == "dangling":
        return _dangling_graph()
    return _weighted_core()


@functools.cache
def weighted(name: str) -> Graph:
    """``graph(name)`` with random per-edge weights in (0.2, 1] and a
    random teleport bias, where it has none of its own."""
    g = graph(name)
    if g.weights is not None:
        return g
    rng = np.random.default_rng(7)
    return Graph.from_edges(g.n, g.src, g.dst,
                            weights=rng.uniform(0.2, 1.0, g.m),
                            bias=rng.uniform(0.5, 1.5, g.n))


@functools.cache
def pallas(name: str, block: int, cap: int, weights: bool = False
           ) -> PallasGraph:
    g = weighted(name) if weights else graph(name)
    pg = PallasGraph.build(g, block=block, tile_cap=cap)
    assert pg.n_blocks >= 3
    return pg


def blocked(x, pg: PallasGraph) -> jnp.ndarray:
    """``(..., n)`` host values → the kernels' ``(n_blocks, ..., block)``
    f32 layout, padding lanes zero."""
    x = np.asarray(x, np.float32)
    pad = np.zeros(x.shape[:-1] + (pg.n_blocks * pg.block,), np.float32)
    pad[..., :pg.n] = x
    pad = pad.reshape(x.shape[:-1] + (pg.n_blocks, pg.block))
    return jnp.asarray(np.moveaxis(pad, -2, 0))


def unblocked(x, pg: PallasGraph) -> np.ndarray:
    x = np.moveaxis(np.asarray(x), 0, -2)
    return x.reshape(x.shape[:-2] + (-1,))[..., :pg.n]


def run_gs_pass(g: Graph, pg: PallasGraph, pr, *, base, bias=None,
                dmass=0.0, frozen=None) -> np.ndarray:
    vmask = blocked(np.ones(g.n), pg)
    wt = pg.tiles_valid if pg.tiles_weight is None else pg.tiles_weight
    out = spmv_gs_pass(
        blocked(pr, pg), pg.inv_out_blocks, vmask,
        vmask if bias is None else blocked(bias, pg),
        jnp.zeros_like(vmask) if frozen is None else blocked(frozen, pg),
        jnp.asarray([[base, D, dmass]], jnp.float32),
        pg.tiles_src_local, pg.tiles_dst_local, pg.tiles_valid, wt,
        pg.tile_src_block, pg.tile_dst_block, block=pg.block)
    return unblocked(out, pg)


def assert_close(out, ref, sweeps: int = 1):
    tol = sweeps * SWEEP_ULPS * EPS * float(np.abs(ref).max())
    err = float(np.abs(np.asarray(out, np.float64) - ref).max())
    assert err <= tol, (err, tol)


def uniform(g: Graph) -> np.ndarray:
    return np.full(g.n, 1.0 / g.n, np.float32)


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda b: "%dx%d" % b)
@pytest.mark.parametrize("name", GRAPHS)
def test_gs_pass_matches_sweep_reference(name, layout):
    g = graph(name)
    pg = pallas(name, *layout)
    pr, base = uniform(g), (1 - D) / g.n
    out = run_gs_pass(g, pg, pr, base=base, bias=g.bias)
    ref = spmv_gs_pass_ref(g, pr, block=pg.block, d=D, base=base,
                           bias=g.bias)
    assert_close(out, ref)


@pytest.mark.parametrize("name", GRAPHS)
def test_gs_pass_freeze_mask_matches_reference(name):
    g = graph(name)
    pg = pallas(name, 128, 128)
    rng = np.random.default_rng(8)
    pr = rng.uniform(0.5, 1.5, g.n).astype(np.float32) / g.n
    frozen = rng.random(g.n) < 0.3
    base = (1 - D) / g.n
    out = run_gs_pass(g, pg, pr, base=base, bias=g.bias, frozen=frozen)
    ref = spmv_gs_pass_ref(g, pr, block=pg.block, d=D, base=base,
                           bias=g.bias, frozen=frozen)
    assert np.array_equal(out[frozen].view(np.uint32),
                          pr[frozen].view(np.uint32))
    assert_close(out[~frozen], ref[~frozen])


@pytest.mark.parametrize("name", GRAPHS)
def test_gs_pass_weighted_biased_dangling_matches_reference(name):
    g = weighted(name)
    pg = pallas(name, 128, 128, weights=True)
    pr, base = uniform(g), (1 - D) / g.n
    dmass = D * 0.25 / g.n  # a quarter of the mass on dangling vertices
    out = run_gs_pass(g, pg, pr, base=base, bias=g.bias, dmass=dmass)
    ref = spmv_gs_pass_ref(g, pr, block=pg.block, d=D, base=base,
                           bias=g.bias, dmass=dmass)
    assert_close(out, ref)


@pytest.mark.parametrize("layout", ((128, 128), (640, 128)),
                         ids=lambda b: "%dx%d" % b)
@pytest.mark.parametrize("rows", (1, 2, 3, 8))
@pytest.mark.parametrize("name", GRAPHS)
def test_gs_pass_multi_matches_sweep_reference(name, rows, layout):
    g = graph(name)
    pg = pallas(name, *layout)
    rng = np.random.default_rng(rows)
    # distinct teleport rows, each with its own dangling mass in its base
    tele = rng.random((rows, g.n))
    tele /= tele.sum(axis=1, keepdims=True)
    base = tele * ((1 - D) + D * rng.uniform(0, 0.2, (rows, 1)))
    pr = tele.astype(np.float32)
    frozen = np.zeros((rows, 1), bool)
    frozen[-1] = rows > 1
    wt = pg.tiles_valid if pg.tiles_weight is None else pg.tiles_weight
    out = unblocked(spmv_gs_pass_multi(
        blocked(pr, pg), pg.inv_out_blocks, blocked(np.ones(g.n), pg),
        jnp.asarray(frozen.T, jnp.float32), blocked(base, pg),
        jnp.asarray([[D]], jnp.float32),
        pg.tiles_src_local, pg.tiles_dst_local, pg.tiles_valid, wt,
        pg.tile_src_block, pg.tile_dst_block, block=pg.block), pg)
    ref = spmv_gs_pass_ref(g, pr, block=pg.block, d=D,
                           base=base.astype(np.float32), frozen=frozen)
    for row in range(rows):
        assert_close(out[row], ref[row])
    if rows > 1:
        assert np.array_equal(out[-1], pr[-1])


def jacobi_sweep(g: Graph, pr) -> np.ndarray:
    """One Jacobi sweep in float64: every source read from the input."""
    inv_out = np.zeros(g.n)
    np.divide(1.0, g.out_degree, out=inv_out, where=g.out_degree > 0)
    w = inv_out[g.src] if g.weights is None else inv_out[g.src] * g.weights
    acc = np.bincount(g.dst, np.asarray(pr, np.float64)[g.src] * w, g.n)
    bias = 1.0 if g.bias is None else g.bias
    return (1 - D) / g.n * bias + D * acc


@pytest.mark.parametrize("name", GRAPHS + ("one_block",))
def test_sweep_reference_differs_from_jacobi(name):
    """On a multi-block graph the fresh reads move the sweep by over 100×
    the tolerance the kernel tests hold, so those tests tell a stale-read
    kernel from a fresh one; on a one-block graph (every source in the
    block being committed) the sweep is Jacobi's."""
    g = rmat_graph(7, avg_degree=5, seed=2) if name == "one_block" \
        else graph(name)
    pr = uniform(g)
    jac = jacobi_sweep(g, pr)
    for block, _ in LAYOUTS:
        ref = spmv_gs_pass_ref(g, pr, block=block, d=D,
                               base=(1 - D) / g.n, bias=g.bias)
        tol = SWEEP_ULPS * EPS * float(np.abs(ref).max())
        gap = float(np.abs(ref - jac).max())
        if name == "one_block":
            assert g.n <= block and gap <= tol, (gap, tol)
        else:
            assert gap > 100 * tol, (block, gap, tol)


@pytest.mark.parametrize("name", GRAPHS)
def test_pallas_nosync_sweeps_compose_reference(name):
    """Five iterations of the registry's ``pallas_nosync`` are five
    reference sweeps, each with the base and the dangling mass the engine
    hands the kernel: the solver calls the kernel as the reference says."""
    g = graph(name)
    sweeps = 5
    r = solve_variant("pallas_nosync", g, d=D, threshold=0.0,
                      max_iter=sweeps, handle_dangling=True, block=128,
                      tile_cap=128)
    assert int(r.iterations) == sweeps
    ref = uniform(g).astype(np.float64)
    for _ in range(sweeps):
        dmass = D * ref[g.out_degree == 0].sum() / g.n
        ref = spmv_gs_pass_ref(g, ref, block=128, d=D, base=(1 - D) / g.n,
                               bias=g.bias, dmass=dmass)
    assert_close(r.pr, ref, sweeps=sweeps)
