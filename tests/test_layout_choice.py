"""The serving engine's tile layout: the block-pair histogram counts the
tiles a layout makes without building it, ``choose_layout`` picks the
layout the step-cost model predicts fastest within one kernel call's
limits, and an explicit layout is built exactly as given."""
import numpy as np
import pytest

from repro.core.solver import build_variant
from repro.graphs import rmat_graph
from repro.graphs.csr import (
    Graph,
    block_pair_counts,
    blocked_tile_stats,
    build_blocked_coo,
    tiles_from_counts,
)
from repro.kernels.spmv import ops
from repro.kernels.spmv.kernel import (
    GROUP,
    MAX_TILES,
    VMEM_CAPACITY,
    multi_pass_vmem_bytes,
)
from repro.kernels.spmv.ops import PallasGraph, choose_layout, step_us
from repro.serving.ppr_engine import PPREngine
from repro.utils.tracing import LAYOUTS


@pytest.fixture(scope="module")
def kron():
    """A Kronecker (R-MAT) graph with the skew of the served graphs."""
    return rmat_graph(13, avg_degree=16, seed=5, dedupe=False)


def _predicted(g, block, cap):
    return blocked_tile_stats(g, block, cap)["n_tiles"] * step_us(block, cap)


@pytest.mark.parametrize("block,cap", [(128, 128), (256, 1024), (384, 256),
                                       (1024, 512)])
def test_histogram_counts_the_tiles_the_build_makes(kron, block, cap):
    keys, counts = block_pair_counts(kron, block)
    n_blocks = -(-kron.n // block)
    assert counts.sum() == kron.m
    assert tiles_from_counts(keys, counts, n_blocks, cap) == \
        build_blocked_coo(kron, block=block, tile_cap=cap).num_tiles


def test_histogram_is_the_same_in_any_chunking(kron):
    whole = block_pair_counts(kron, 128)
    for a, b in zip(whole, block_pair_counts(kron, 128, chunk_edges=10_000)):
        np.testing.assert_array_equal(a, b)


def test_choice_beats_the_old_default_on_a_kronecker_graph(kron):
    block, cap = choose_layout(kron)
    assert _predicted(kron, block, cap) < _predicted(kron, 256, 1024)
    # no weighed layout is predicted faster than the one chosen
    for b in (128, 256, 512, 1024, 2048):
        for c in (128, 256, 512, 1024):
            assert _predicted(kron, block, cap) <= _predicted(kron, b, c)


@pytest.mark.parametrize("slots", [1, 8, 64])
def test_choice_stays_within_one_kernel_call(kron, slots):
    block, cap = choose_layout(kron, rows=slots)
    n_blocks = -(-kron.n // block)
    assert block % 128 == 0 and cap in (128, 256, 512, 1024)
    assert blocked_tile_stats(kron, block, cap)["n_tiles"] <= MAX_TILES
    assert multi_pass_vmem_bytes(n_blocks, block, cap, slots) <= VMEM_CAPACITY


def test_choice_honours_a_binding_tile_limit(kron, monkeypatch):
    free = choose_layout(kron)
    tiles = blocked_tile_stats(kron, *free)["n_tiles"]
    monkeypatch.setattr(ops, "MAX_TILES", tiles - 1)
    block, cap = choose_layout(kron)
    assert (block, cap) != free
    assert blocked_tile_stats(kron, block, cap)["n_tiles"] < tiles
    monkeypatch.setattr(ops, "MAX_TILES", 0)
    with pytest.raises(ValueError, match="no tile layout"):
        choose_layout(kron)


def test_choice_honours_the_vmem_limit(kron, monkeypatch):
    block, cap = choose_layout(kron)
    need = multi_pass_vmem_bytes(-(-kron.n // block), block, cap, 8)
    monkeypatch.setattr(ops, "VMEM_CAPACITY", need - 1)
    b2, c2 = choose_layout(kron)
    assert multi_pass_vmem_bytes(-(-kron.n // b2), b2, c2, 8) < need


@pytest.mark.parametrize("slots", [1, 8, 64])
def test_choice_ignores_group_padding(kron, slots):
    """The pick weighs the tiles build_blocked_coo makes, as it did before
    the Gauss–Seidel kernels ran whole groups: the pads a build adds, and
    the tile bodies a group keeps in VMEM, move no pick."""
    assert choose_layout(kron, rows=slots) == (256, 128)


def test_choice_is_deterministic(kron):
    rng = np.random.default_rng(0)
    order = rng.permutation(kron.m)
    shuffled = Graph.from_edges(kron.n, kron.src[order], kron.dst[order])
    assert choose_layout(kron) == choose_layout(kron) == choose_layout(shuffled)


@pytest.mark.parametrize("n,edges", [
    (1, []), (5, []), (2, [(0, 1), (1, 0)]), (64, [(i, (3 * i) % 64)
                                                   for i in range(64)]),
])
def test_choice_is_valid_on_tiny_graphs(n, edges):
    e = np.asarray(edges, np.int32).reshape(-1, 2)
    g = Graph.from_edges(n, e[:, 0], e[:, 1])
    block, cap = choose_layout(g)
    assert (block, cap) == (128, 128)
    pg = PallasGraph.build(g, block=block, tile_cap=cap)
    assert pg.layout.tiles >= 1 and 0.0 <= pg.layout.fill <= 1.0


def test_engine_without_a_layout_chooses_and_records_it(kron):
    eng = PPREngine(kron, slots=8, backend="pallas")
    lay = eng.layout
    assert lay.chosen and (lay.block, lay.tile_cap) == choose_layout(kron)
    assert lay.tiles == blocked_tile_stats(kron, lay.block,
                                           lay.tile_cap)["n_tiles"]
    assert lay.tiles + lay.pad_tiles == \
        eng._backend.pg.tiles_src_local.shape[0]
    assert lay.tiles_per_step == GROUP
    assert lay.fill == pytest.approx(kron.m / (lay.tiles * lay.tile_cap))
    assert eng.cache_block == lay.block
    assert LAYOUTS["spmv_gs_pass_multi"] == lay._asdict()
    assert PPREngine(kron, backend="jax").layout is None


@pytest.mark.parametrize("opts,expect", [
    (dict(block=64, tile_cap=256), (64, 256)),
    (dict(block=128), (128, 1024)),
    (dict(tile_cap=128), (256, 128)),
])
def test_explicit_layout_is_built_as_before(opts, expect):
    g = rmat_graph(8, avg_degree=6, seed=7)
    eng = PPREngine(g, slots=4, backend="pallas", **opts)
    assert not eng.layout.chosen
    assert (eng.layout.block, eng.layout.tile_cap) == expect
    pg, ref = eng._backend.pg, build_blocked_coo(g, *expect)
    # each dst block's run starts with its real tiles, then pads to a group
    real = np.concatenate([
        np.flatnonzero(np.asarray(pg.tile_dst_block) == j)[:n]
        for j, n in enumerate(np.bincount(ref.tile_dst_block))])
    for name in ("tiles_src_local", "tiles_dst_local", "tiles_valid",
                 "tile_src_block", "tile_dst_block"):
        np.testing.assert_array_equal(np.asarray(getattr(pg, name))[real],
                                      getattr(ref, name))


def test_global_gs_solve_records_its_layout():
    g = rmat_graph(7, avg_degree=5, seed=1)
    v, pg = build_variant("pallas_nosync", g, block=32, tile_cap=64)
    v.run(pg, threshold=1e-6)
    real = build_blocked_coo(g, block=32, tile_cap=64).num_tiles
    assert LAYOUTS["spmv_gs_pass"] == dict(
        block=32, tile_cap=64, tiles=real, fill=g.m / (real * 64),
        chosen=False, pad_tiles=pg.tiles_src_local.shape[0] - real,
        tiles_per_step=GROUP)


def test_serve_prints_the_layout(capsys):
    from repro.launch.pagerank_run import serve_main

    assert serve_main(["--backend", "pallas", "--scale-down", "2048",
                       "--queries", "2", "--slots", "8"]) == 0
    line = [s for s in capsys.readouterr().out.splitlines()
            if s.startswith("layout:")]
    assert len(line) == 1 and line[0].endswith("(chosen)")
    assert "block=128" in line[0] and "fill=" in line[0]

