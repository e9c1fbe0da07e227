"""PPR subsystem tier: teleport construction, the uniform-seed ↔ global
round-trip (teleport linearity, the acceptance invariant), push-solver
certificates vs the batched oracle, the multi-vector Pallas pass, and the
continuous-batching serving engine (mixed batches, warm starts, slot
recycling, per-slot early exit)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, strategies as st
except ImportError:  # pragma: no cover — container has no hypothesis
    from _hypothesis_compat import given, strategies as st

from repro.core import DeviceGraph, PartitionedGraph, l1_norm, pagerank_numpy
from repro.core.solver import solve_variant
from repro.graphs import rmat_graph
from repro.graphs.csr import Graph
from repro.kernels.spmv import PallasGraph, spmv_gs_pass, spmv_gs_pass_multi
from repro.ppr import (
    normalize_seeds,
    ppr_barrier,
    ppr_nosync,
    ppr_numpy,
    ppr_pallas,
    ppr_push,
    teleport_from_seeds,
    topk,
)
from repro.serving.ppr_engine import PPREngine, PPRQuery

PPR_VARIANTS = ("ppr_barrier", "ppr_nosync", "ppr_pallas", "ppr_push")
OPTS = dict(threads=4, block=64, tile_cap=128, interpret=True)


@st.composite
def small_graphs(draw):
    n = draw(st.integers(8, 64))
    m = draw(st.integers(n, 4 * n))
    edges = draw(
        st.lists(
            st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
            min_size=m, max_size=m,
        )
    )
    src = np.array([e[0] for e in edges], dtype=np.int32)
    dst = np.array([e[1] for e in edges], dtype=np.int32)
    return Graph.from_edges(n, src, dst)


# ---------------------------------------------------------------------------
# teleport construction
# ---------------------------------------------------------------------------


def test_normalize_seeds_forms():
    assert normalize_seeds(None) == ((),)
    assert normalize_seeds(3) == ((3,),)
    assert normalize_seeds((3, 5)) == ((3, 5),)
    assert normalize_seeds([(3,), (5, 6), ()]) == ((3,), (5, 6), ())
    assert normalize_seeds([]) == ((),)


def test_teleport_rows_are_distributions():
    t = teleport_from_seeds([(3,), (5, 6), ()], n=10, n_pad=16)
    assert t.shape == (3, 16)
    np.testing.assert_allclose(t.sum(axis=1), 1.0)
    assert t[0, 3] == 1.0 and t[1, 5] == t[1, 6] == 0.5
    np.testing.assert_allclose(t[2, :10], 0.1)
    assert (t[:, 10:] == 0).all()  # padding columns never get teleport mass


def test_teleport_duplicate_seeds_stay_stochastic():
    """Repeated seeds are a seed SET: the row must stay a distribution (a
    fancy-index assignment would silently drop the duplicate's mass) and
    share its fixed point with the deduplicated query — which is also what
    the serving engine's warm cache keys on."""
    t = teleport_from_seeds([(3, 3, 5)], n=10)
    np.testing.assert_allclose(t.sum(axis=1), 1.0)
    np.testing.assert_allclose(t[0], teleport_from_seeds([(3, 5)], n=10)[0])


def test_teleport_rejects_out_of_range_seed():
    with pytest.raises(ValueError, match="out of range"):
        teleport_from_seeds([(11,)], n=10)


# ---------------------------------------------------------------------------
# the acceptance invariant: uniform-seed PPR == global PageRank
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("handle_dangling", (False, True))
def test_uniform_seed_row_equals_global_pagerank(handle_dangling):
    """Teleport linearity (float64 oracle): a uniform teleport row IS the
    global PageRank problem — L1 < 1e-6 is the subsystem's acceptance bar."""
    g = rmat_graph(8, avg_degree=5, seed=3)
    ref, _ = pagerank_numpy(g, threshold=1e-12,
                            handle_dangling=handle_dangling)
    pr, _ = ppr_numpy(g, teleport_from_seeds(None, g.n), threshold=1e-12,
                      handle_dangling=handle_dangling)
    assert l1_norm(pr[0], ref) < 1e-6


@given(small_graphs())
def test_property_uniform_row_matches_global(g):
    """The uniform round-trip holds over random graphs, batched alongside
    arbitrary seed rows (the batch must not couple rows)."""
    if g.n < 3:
        return
    ref, _ = pagerank_numpy(g, threshold=1e-12, handle_dangling=True)
    seeds = [(), (0,), (1, 2)]
    pr, _ = ppr_numpy(g, teleport_from_seeds(seeds, g.n), threshold=1e-12,
                      handle_dangling=True)
    assert l1_norm(pr[0], ref) < 1e-6


@given(small_graphs())
def test_property_teleport_linearity(g):
    """PPR is linear in the teleport vector: solving the 50/50 mixture of two
    seed rows equals mixing the two solutions.  (Only without dangling
    redistribution — re-teleporting dangling mass onto the row's own seeds
    makes the operator teleport-dependent, so linearity is deliberately
    scoped to the leaky convention.)"""
    if g.n < 4:
        return
    t = teleport_from_seeds([(0,), (1, 3)], g.n)
    mix = 0.5 * t[0] + 0.5 * t[1]
    pr, _ = ppr_numpy(g, np.stack([t[0], t[1], mix]), threshold=1e-13)
    assert np.abs(0.5 * pr[0] + 0.5 * pr[1] - pr[2]).sum() < 1e-9


# ---------------------------------------------------------------------------
# batched engine variants vs the float64 oracle (multi-seed batches)
# ---------------------------------------------------------------------------

SEED_BATCH = [(3,), (10, 11, 12), (), (7, 3)]


@pytest.mark.parametrize("vname", ("ppr_barrier", "ppr_nosync", "ppr_pallas"))
@pytest.mark.parametrize("handle_dangling", (False, True))
def test_batched_variants_match_oracle_per_row(vname, handle_dangling):
    g = rmat_graph(7, avg_degree=5, seed=5)
    oracle, _ = ppr_numpy(g, teleport_from_seeds(SEED_BATCH, g.n),
                          threshold=1e-12, handle_dangling=handle_dangling)
    r = solve_variant(vname, g, threshold=1e-9, seeds=SEED_BATCH,
                      handle_dangling=handle_dangling, **OPTS)
    pr = np.asarray(r.pr, np.float64)
    assert pr.shape == (len(SEED_BATCH), g.n)
    for i in range(len(SEED_BATCH)):
        assert np.abs(pr[i] - oracle[i]).sum() < 1e-5, (vname, i)


def test_batched_row_freeze_exits_rows_independently():
    """Per-row convergence: a batch of one trivially-easy row (dangling
    seed, converges immediately) and one hard row must still solve the hard
    row to the oracle — freezing the easy row must not stall or corrupt it."""
    g = rmat_graph(7, avg_degree=5, seed=5)
    sink = int(np.flatnonzero(g.out_degree == 0)[0]) if (
        g.out_degree == 0).any() else 0
    seeds = [(sink,), ()]
    oracle, _ = ppr_numpy(g, teleport_from_seeds(seeds, g.n), threshold=1e-12)
    r = ppr_barrier(DeviceGraph.from_graph(g),
                    teleport_from_seeds(seeds, g.n), threshold=1e-9)
    pr = np.asarray(r.pr, np.float64)
    for i in range(2):
        assert np.abs(pr[i] - oracle[i]).sum() < 1e-5


def test_ppr_nosync_partition_count_invariance():
    """Lemma-2 carry-over: the batched no-sync fixed point must not depend
    on the partition count."""
    g = rmat_graph(7, avg_degree=5, seed=9)
    t = teleport_from_seeds([(3,), ()], g.n)
    base = None
    for p in (2, 5):
        r = ppr_nosync(PartitionedGraph.from_graph(g, p=p), t, threshold=1e-9)
        pr = np.asarray(r.pr, np.float64)
        if base is None:
            base = pr
        else:
            assert np.abs(pr - base).sum() < 1e-5


# ---------------------------------------------------------------------------
# push solver: certificates and top-k agreement with the batched oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("handle_dangling", (False, True))
def test_push_certificate_bounds_true_error(handle_dangling):
    g = rmat_graph(8, avg_degree=6, seed=1)
    for seeds in ((3,), (10, 11), ()):
        res = ppr_push(g, seeds, rmax=1e-7, handle_dangling=handle_dangling)
        ref = ppr_numpy(g, teleport_from_seeds([seeds], g.n), threshold=1e-13,
                        handle_dangling=handle_dangling)[0][0]
        err = np.abs(res.est - ref).sum()
        assert err <= res.l1_bound + 1e-12, (seeds, err, res.l1_bound)
        # push estimates are always lower bounds (unpushed mass is missing)
        assert (res.est <= ref + 1e-12).all()


@given(small_graphs())
def test_property_push_topk_agrees_with_oracle_within_bound(g):
    """Every oracle top-k vertex the push answer misses must be within the
    push residual bound of the push answer's k-th value — the sharpest
    claim the certificate supports under ties."""
    if g.n < 8:
        return
    k = 5
    res = ppr_push(g, (0,), rmax=1e-9, handle_dangling=True)
    ref = ppr_numpy(g, teleport_from_seeds([(0,)], g.n), threshold=1e-13,
                    handle_dangling=True)[0][0]
    idx, vals = res.topk(k)
    kth = vals[-1]
    for v in np.argsort(ref)[::-1][:k]:
        if v not in idx:
            assert ref[v] <= kth + 2 * res.l1_bound + 1e-12


def test_push_rejects_batched_seed_spec():
    """A nested (multi-row) spec must raise, not silently answer row 0 —
    batches go through the registry variant, which loops rows."""
    g = rmat_graph(6, avg_degree=4, seed=0)
    with pytest.raises(ValueError, match="one seed set per call"):
        ppr_push(g, [(1,), (2,)])
    batched = solve_variant("ppr_push", g, threshold=1e-8,
                            seeds=[(1,), (2,)])
    assert np.asarray(batched.pr).shape == (2, g.n)


def test_push_empty_graph():
    g = Graph.from_edges(0, np.zeros(0, np.int32), np.zeros(0, np.int32))
    res = ppr_push(g, ())
    assert res.est.shape == (0,) and res.rounds == 0


def test_topk_tie_break_deterministic():
    est = np.asarray([0.5, 0.1, 0.1, 0.3])
    idx, vals = topk(est, 3)
    assert idx.tolist() == [0, 3, 1]  # ties broken by vertex id
    np.testing.assert_allclose(vals, [0.5, 0.3, 0.1])


# ---------------------------------------------------------------------------
# multi-vector Pallas pass
# ---------------------------------------------------------------------------


def test_gs_pass_multi_b1_equals_single_vector_pass():
    g = rmat_graph(7, avg_degree=5, seed=2)
    pg = PallasGraph.build(g, block=64, tile_cap=128)
    n_blocks, block = pg.inv_out_blocks.shape
    n_pad = n_blocks * block
    vmask = (jnp.arange(n_pad) < g.n).astype(jnp.float32).reshape(
        n_blocks, block)
    pr0 = jnp.full((n_blocks, block), 1.0 / g.n, jnp.float32) * vmask
    d, base = 0.85, 0.15 / g.n
    # tiles_valid doubles as the weights operand on unweighted graphs
    tiles = (pg.tiles_src_local, pg.tiles_dst_local, pg.tiles_valid,
             pg.tiles_valid, pg.tile_src_block, pg.tile_dst_block)
    out1 = spmv_gs_pass(pr0, pg.inv_out_blocks, vmask, vmask,
                        jnp.zeros_like(vmask),
                        jnp.asarray([[base, d, 0.0]], jnp.float32), *tiles,
                        block=block, interpret=True)
    b = 3
    prb = jnp.broadcast_to(pr0[:, None, :], (n_blocks, b, block))
    baseb = jnp.broadcast_to((base * vmask)[:, None, :], (n_blocks, b, block))
    outm = spmv_gs_pass_multi(
        prb, pg.inv_out_blocks, vmask, jnp.zeros((1, b), jnp.float32), baseb,
        jnp.asarray([[d]], jnp.float32), *tiles, block=block, interpret=True)
    for row in range(b):
        assert float(jnp.max(jnp.abs(outm[:, row, :] - out1))) < 1e-6


def test_gs_pass_multi_frozen_rows_held():
    g = rmat_graph(7, avg_degree=5, seed=2)
    pg = PallasGraph.build(g, block=64, tile_cap=128)
    n_blocks, block = pg.inv_out_blocks.shape
    vmask = (jnp.arange(n_blocks * block) < g.n).astype(jnp.float32).reshape(
        n_blocks, block)
    b = 2
    prb = jnp.broadcast_to((jnp.full((n_blocks, block), 1.0 / g.n) *
                            vmask)[:, None, :], (n_blocks, b, block)
                           ).astype(jnp.float32)
    baseb = jnp.broadcast_to((0.15 / g.n * vmask)[:, None, :],
                             (n_blocks, b, block)).astype(jnp.float32)
    frozen = jnp.asarray([[1.0, 0.0]], jnp.float32)  # row 0 frozen, row 1 live
    out = spmv_gs_pass_multi(
        prb, pg.inv_out_blocks, vmask, frozen, baseb,
        jnp.asarray([[0.85]], jnp.float32),
        pg.tiles_src_local, pg.tiles_dst_local, pg.tiles_valid,
        pg.tiles_valid, pg.tile_src_block, pg.tile_dst_block, block=block,
        interpret=True)
    assert float(jnp.max(jnp.abs(out[:, 0, :] - prb[:, 0, :]))) == 0.0
    assert float(jnp.max(jnp.abs(out[:, 1, :] - prb[:, 1, :]))) > 0.0


def test_ppr_pallas_empty_graph():
    g = Graph.from_edges(0, np.zeros(0, np.int32), np.zeros(0, np.int32))
    r = ppr_pallas(PallasGraph.build(g, block=16, tile_cap=32),
                   np.zeros((2, 0)), interpret=True)
    assert r.pr.shape == (2, 0) and int(r.iterations) == 0


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------


def _oracle_band_check(g, resp, k):
    """Tie-robust oracle agreement: answered vertices sit in the oracle's
    top-k value band and carry the oracle's scores."""
    ref = ppr_numpy(g, teleport_from_seeds([resp.seeds], g.n),
                    threshold=1e-12)[0][0]
    kth = np.sort(ref)[::-1][k - 1]
    assert (ref[resp.indices] >= kth - 1e-6).all(), resp.seeds
    assert np.abs(resp.values - ref[resp.indices]).max() < 1e-5, resp.seeds


@pytest.mark.parametrize("backend,opts", [
    ("jax", {}),
    ("pallas", dict(block=64, tile_cap=256, interpret=True)),
    ("pallas", dict(interpret=True)),  # the layout choose_layout picks
])
def test_engine_mixed_batch_matches_oracle(backend, opts):
    g = rmat_graph(8, avg_degree=6, seed=7)
    eng = PPREngine(g, slots=3, threshold=1e-7, backend=backend, **opts)
    k = 8
    seed_sets = [(3,), (10, 11), (), (5,), (42, 7, 9)]  # > slots: recycling
    responses = eng.drain([PPRQuery(qid=i, seeds=s, top_k=k)
                           for i, s in enumerate(seed_sets)])
    assert len(responses) == len(seed_sets)
    assert sorted(r.qid for r in responses) == list(range(len(seed_sets)))
    for r in responses:
        _oracle_band_check(g, r, k)


def test_engine_warm_start_reuses_cached_vector():
    g = rmat_graph(8, avg_degree=6, seed=7)
    eng = PPREngine(g, slots=2, threshold=1e-7)
    cold = eng.drain([PPRQuery(qid=0, seeds=(3,), top_k=5)])[0]
    warm = eng.drain([PPRQuery(qid=1, seeds=(3,), top_k=5)])[0]
    assert not cold.warm_start and warm.warm_start
    assert eng.warm_hits == 1
    # a warm row starts converged: it exits on its first step chunk
    assert warm.iterations <= eng.iters_per_step
    assert warm.iterations < cold.iterations
    assert warm.indices.tolist() == cold.indices.tolist()


def test_engine_rejects_when_full_then_recycles():
    g = rmat_graph(7, avg_degree=5, seed=1)
    eng = PPREngine(g, slots=1, threshold=1e-6)
    assert eng.submit(PPRQuery(qid=0, seeds=(2,)))
    assert not eng.submit(PPRQuery(qid=1, seeds=(4,)))  # batch full
    done = []
    for _ in range(10_000):
        done += eng.step()
        if done:
            break
    assert done and done[0].qid == 0
    assert eng.submit(PPRQuery(qid=1, seeds=(4,)))  # slot recycled


def test_engine_per_slot_early_exit():
    """A dangling-seed query (converges in one push of mass) harvested while
    a uniform query is still iterating — per-slot exit, not batch exit."""
    g = rmat_graph(8, avg_degree=6, seed=7)
    sinks = np.flatnonzero(g.out_degree == 0)
    if not sinks.size:
        pytest.skip("surrogate has no dangling vertex")
    eng = PPREngine(g, slots=2, threshold=1e-8, iters_per_step=2)
    assert eng.submit(PPRQuery(qid=0, seeds=(int(sinks[0]),), top_k=3))
    assert eng.submit(PPRQuery(qid=1, seeds=(), top_k=3))
    first = []
    while not first:
        first = eng.step()
    assert [r.qid for r in first] == [0]  # easy row exits first
    assert eng.active_count == 1  # hard row still resident
    rest = eng.drain([])
    assert [r.qid for r in rest] == [1]


def test_engine_reset_clears_warm_cache_but_keeps_jit():
    g = rmat_graph(7, avg_degree=5, seed=1)
    eng = PPREngine(g, slots=2, threshold=1e-6)
    eng.drain([PPRQuery(qid=0, seeds=(2,))])
    assert eng._cache
    eng.reset()
    assert not eng._cache and eng.warm_hits == 0
    again = eng.drain([PPRQuery(qid=1, seeds=(2,))])[0]
    assert not again.warm_start  # measured run starts cold
    assert eng.submit(PPRQuery(qid=2, seeds=(3,)))
    with pytest.raises(RuntimeError, match="active"):
        eng.reset()


def test_engine_rejects_unknown_backend_and_empty_graph():
    g = rmat_graph(6, avg_degree=4, seed=0)
    with pytest.raises(ValueError, match="backend"):
        PPREngine(g, backend="cuda")
    empty = Graph.from_edges(0, np.zeros(0, np.int32), np.zeros(0, np.int32))
    with pytest.raises(ValueError, match="empty"):
        PPREngine(empty)


def test_engine_malformed_query_cannot_poison_the_batch():
    """An out-of-range seed must raise BEFORE any state mutates: submit
    leaks no slot, and drain validates the whole batch up front instead of
    aborting mid-flight and discarding harvested responses."""
    from repro.serving.ppr_engine import make_query_stream

    g = rmat_graph(7, avg_degree=5, seed=1)
    eng = PPREngine(g, slots=2, threshold=1e-6)
    bad = PPRQuery(qid=9, seeds=(g.n + 5,))
    with pytest.raises(ValueError, match="out of range"):
        eng.submit(bad)
    assert eng.active_count == 0  # no half-allocated slot
    with pytest.raises(ValueError, match="out of range"):
        eng.drain([PPRQuery(qid=0, seeds=(2,)), bad])
    assert eng.active_count == 0  # nothing started before validation
    resp = eng.drain([PPRQuery(qid=0, seeds=(2,))])  # engine still healthy
    assert [r.qid for r in resp] == [0]
    # and the stream generator survives graphs too small for multi-seed sets
    for n in (1, 2, 3):
        qs = make_query_stream(n, 30, seed=3)
        assert len(qs) == 30
        assert all(max(q.seeds, default=0) < n for q in qs)
