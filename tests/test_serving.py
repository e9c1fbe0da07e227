"""Serving runtime: admission/backpressure, deadlines, result-cache
invalidation (the stale-answer regression), mesh-sharded identity, and the
closed-loop load generator."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.graphs import Graph, rmat_graph
from repro.ppr import ppr_numpy, teleport_from_seeds
from repro.serving.loadgen import (
    LoadConfig, VirtualClock, _percentile, make_workload, run_closed_loop,
    zipf_weights,
)
from repro.serving.ppr_engine import PPREngine, PPRQuery, make_query_stream
from repro.serving.runtime import ServingRuntime


def _engine(g, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("threshold", 1e-7)
    return PPREngine(g, **kw)


@pytest.fixture(scope="module")
def g64():
    return rmat_graph(6, avg_degree=6, seed=3)


# ---------------------------------------------------------------------------
# admission queue: backpressure, deadlines
# ---------------------------------------------------------------------------


def test_queue_full_rejects(g64):
    rt = ServingRuntime(_engine(g64), queue_depth=2)
    outcomes = [rt.offer(PPRQuery(qid=i, seeds=(i,))).status for i in range(4)]
    assert outcomes == ["queued", "queued", "rejected", "rejected"]
    assert rt.metrics.count("rejected") == 2
    assert rt.metrics.count("offered") == 4
    # the queue drains through pump: a later offer is admitted again
    while rt.pending:
        rt.pump()
    assert rt.offer(PPRQuery(qid=9, seeds=(9,))).status == "queued"


def test_deadline_expires_instead_of_solving(g64):
    vc = VirtualClock()
    rt = ServingRuntime(_engine(g64), deadline_s=0.5, clock=vc.now)
    rt.offer(PPRQuery(qid=0, seeds=(1,)))
    vc.advance(1.0)  # waited past its deadline before any slot freed
    responses = rt.pump()
    assert responses == []
    assert rt.metrics.count("expired") == 1
    assert rt.pending == 0  # dropped, never occupied a slot
    # a fresh offer inside the deadline window is solved normally
    rt.offer(PPRQuery(qid=1, seeds=(1,)))
    out = []
    while rt.pending:
        out += rt.pump()
    assert [r.qid for r in out] == [1]


# ---------------------------------------------------------------------------
# result cache: hits, evictions, and the stale-answer regression
# ---------------------------------------------------------------------------


def test_result_cache_hit_and_eviction(g64):
    rt = ServingRuntime(_engine(g64), result_cache_size=2)
    first = rt.serve([PPRQuery(qid=i, seeds=(i,), top_k=5) for i in range(3)])
    assert rt.metrics.count("cache_evictions") == 1
    assert rt.result_cache_len == 2
    # exactly one of the three answers was evicted (which one depends on
    # convergence order); the resident two are served from cache byte-equal
    # to the originally harvested response, with zero slot time
    statuses = {}
    for i in range(3):
        adm = rt.offer(PPRQuery(qid=10 + i, seeds=(i,), top_k=5))
        statuses[i] = adm.status
        if adm.status == "cached":
            assert adm.response.cached and adm.response.iterations == 0
            # no iteration was warm-started — cached alone marks the hit
            assert not adm.response.warm_start
            ref = next(r for r in first if r.seeds == (i,))
            np.testing.assert_array_equal(adm.response.indices, ref.indices)
            np.testing.assert_array_equal(adm.response.values, ref.values)
    assert sorted(statuses.values()) == ["cached", "cached", "queued"]
    assert rt.metrics.count("cache_hits") == 2


def _two_community_graph(n=128, block=64):
    """Two disconnected rings: an update in community B must not invalidate
    community A's cached answer (disjoint weak components)."""
    half = n // 2
    src = np.concatenate([np.arange(half), np.arange(half, n)])
    dst = np.concatenate([(np.arange(half) + 1) % half,
                          half + (np.arange(half) + 1) % half])
    return Graph.from_edges(n, src, dst), half, block


def _assert_matches_oracle(rt, fresh, seeds, k=8):
    """The re-solved answer matches the float64 oracle on the CURRENT
    (post-update) graph."""
    ref = ppr_numpy(rt.engine.g, teleport_from_seeds([seeds], rt.engine.g.n),
                    threshold=1e-12)[0][0]
    kth = np.sort(ref)[::-1][k - 1]
    assert (ref[fresh.indices] >= kth - 1e-6).all()
    assert np.abs(fresh.values - ref[fresh.indices]).max() < 1e-5


def test_stale_cached_topk_never_served_after_update():
    g, half, block = _two_community_graph()
    rt = ServingRuntime(_engine(g, block=block))
    rt.serve([PPRQuery(qid=0, seeds=(5,), top_k=8),
              PPRQuery(qid=1, seeds=(70,), top_k=8)])
    assert rt.result_cache_len == 2

    # shortcut edge inside community B only: A's component is untouched
    delta, _ = rt.apply_updates(adds=np.array([[70, 90]]))
    assert delta.num_ops == 1
    assert rt.metrics.count("cache_invalidations") == 1

    # community A disjoint from every touched vertex: still served exactly
    assert rt.offer(PPRQuery(qid=2, seeds=(5,), top_k=8)).status == "cached"
    # community B: the stale answer must NOT come back — it is re-solved
    # against the updated graph and matches the float64 oracle on it
    adm = rt.offer(PPRQuery(qid=3, seeds=(70,), top_k=8))
    assert adm.status == "queued"
    out = []
    while rt.pending:
        out += rt.pump()
    (fresh,) = [r for r in out if r.qid == 3]
    assert not fresh.cached
    _assert_matches_oracle(rt, fresh, (70,))


def test_connected_graph_invalidates_transitively():
    """THE unsoundness regression: on one connected ring, an update whose
    endpoints sit far from a cached entry's seeds AND answered vertices (a
    different dst block entirely) still perturbs the entry's fixed point
    transitively — it must be dropped, not served as an exact answer."""
    n, block = 128, 64
    g = Graph.from_edges(n, np.arange(n), (np.arange(n) + 1) % n)
    rt = ServingRuntime(_engine(g, block=block))
    rt.serve([PPRQuery(qid=0, seeds=(5,), top_k=8)])
    assert rt.result_cache_len == 1

    # both endpoints in block 1; the entry's seeds/top-k all live in block
    # 0 (vertices 5..12) — a dst-block intersection test would keep it
    delta, _ = rt.apply_updates(adds=np.array([[70, 90]]))
    assert not set(np.r_[delta.touched_src, delta.touched_dst] // block) & {0}
    assert rt.metrics.count("cache_invalidations") == 1
    adm = rt.offer(PPRQuery(qid=1, seeds=(5,), top_k=8))
    assert adm.status == "queued"
    out = []
    while rt.pending:
        out += rt.pump()
    (fresh,) = [r for r in out if r.qid == 1]
    assert not fresh.cached
    _assert_matches_oracle(rt, fresh, (5,))


def test_deletion_invalidates_through_old_graph_reachability():
    """Deleting the only edge that BRIDGED two components must invalidate
    entries upstream of it even though the new graph no longer connects
    them — reachability is judged on the union of old and new graphs."""
    n = 64
    # ring over [0, 32) plus a bridge 5 -> 40 and a chain 40 -> 41
    half = 32
    src = np.r_[np.arange(half), [5, 40]]
    dst = np.r_[(np.arange(half) + 1) % half, [40, 41]]
    g = Graph.from_edges(n, src, dst)
    rt = ServingRuntime(_engine(g))
    rt.serve([PPRQuery(qid=0, seeds=(5,), top_k=8)])
    rt.apply_updates(dels=np.array([[5, 40]]))
    assert rt.metrics.count("cache_invalidations") == 1
    assert rt.offer(PPRQuery(qid=1, seeds=(5,), top_k=8)).status == "queued"


def test_handle_dangling_drops_whole_cache():
    """Redistributed dangling mass couples disconnected components, so with
    handle_dangling the component survival argument is off: any update
    drops every entry, even in an untouched component."""
    g2, half, block = _two_community_graph()
    # append a dangling (isolated) vertex so redistribution is live
    g = Graph.from_edges(g2.n + 1, g2.src, g2.dst)
    rt = ServingRuntime(_engine(g, handle_dangling=True))
    rt.serve([PPRQuery(qid=0, seeds=(5,), top_k=8),
              PPRQuery(qid=1, seeds=(70,), top_k=8)])
    rt.apply_updates(adds=np.array([[70, 90]]))
    assert rt.metrics.count("cache_invalidations") == 2
    assert rt.result_cache_len == 0
    assert rt.offer(PPRQuery(qid=2, seeds=(5,), top_k=8)).status == "queued"


def test_runtime_replaces_and_closes_update_callback(g64):
    """Wrapping one engine in a second runtime must not accumulate
    invalidation hooks (dead runtimes would be kept alive and re-invalidated
    on every update), and close() detaches idempotently."""
    eng = _engine(g64)
    rt1 = ServingRuntime(eng)
    assert eng.update_callbacks == [rt1._invalidate]
    rt2 = ServingRuntime(eng)
    assert eng.update_callbacks == [rt2._invalidate]
    rt2.close()
    assert eng.update_callbacks == []
    rt2.close()  # idempotent


def test_global_entry_invalidated_by_any_update():
    g, half, block = _two_community_graph()
    rt = ServingRuntime(_engine(g, block=block))
    rt.serve([PPRQuery(qid=0, seeds=(), top_k=8)])  # global PageRank row
    rt.apply_updates(adds=np.array([[70, 90]]))
    # a structural change anywhere perturbs the global fixed point
    assert rt.offer(PPRQuery(qid=1, seeds=(), top_k=8)).status == "queued"


# ---------------------------------------------------------------------------
# mesh sharding: 1-device identity in-process, 8-way exactness in subprocess
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,opts", [
    ("jax", {}),
    ("pallas", dict(block=16, tile_cap=64, interpret=True)),
])
def test_mesh1_topk_identical_to_unsharded(g64, backend, opts):
    import jax
    from jax.sharding import AxisType

    qs = make_query_stream(g64.n, 6, top_k=8, seed=0)
    plain = _engine(g64, backend=backend, **opts).drain(qs)
    mesh = jax.make_mesh((1,), ("batch",), axis_types=(AxisType.Auto,))
    sharded = _engine(g64, backend=backend, mesh=mesh, **opts).drain(qs)
    for a, b in zip(sorted(plain, key=lambda r: r.qid),
                    sorted(sharded, key=lambda r: r.qid)):
        np.testing.assert_array_equal(a.indices, b.indices)
        np.testing.assert_array_equal(a.values, b.values)  # bit-identical
        assert a.iterations == b.iterations


_MESH_SCRIPT = textwrap.dedent(
    """
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.graphs import rmat_graph
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.ppr_engine import PPREngine, make_query_stream

    g = rmat_graph(7, avg_degree=6, seed=3)
    qs = make_query_stream(g.n, 12, top_k=8, seed=1)
    plain = PPREngine(g, slots=8, threshold=1e-7).drain(qs)
    mesh = make_serving_mesh(8)
    assert mesh.devices.size == 8, mesh
    sharded = PPREngine(g, slots=8, threshold=1e-7, mesh=mesh).drain(qs)
    out = {"shards": int(mesh.devices.size), "exact": True}
    for a, b in zip(sorted(plain, key=lambda r: r.qid),
                    sorted(sharded, key=lambda r: r.qid)):
        if not (np.array_equal(a.indices, b.indices)
                and np.array_equal(a.values, b.values)):
            out["exact"] = False
    print(json.dumps(out))
    """
)


@pytest.mark.slow
@pytest.mark.subprocess
def test_mesh8_sharded_matches_single_device():
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], capture_output=True, text=True,
        env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["shards"] == 8
    assert out["exact"], "8-way sharded top-k diverged from single device"


# ---------------------------------------------------------------------------
# engine observability counters (the silently-dropped-submit fix)
# ---------------------------------------------------------------------------


def test_engine_submit_rejections_and_occupancy(g64):
    eng = _engine(g64, slots=2)
    assert eng.submit(PPRQuery(qid=0, seeds=(1,)))
    assert eng.submit(PPRQuery(qid=1, seeds=(2,)))
    assert not eng.submit(PPRQuery(qid=2, seeds=(3,)))  # batch full
    assert eng.submit_rejections == 1
    eng.step()
    assert eng.slot_occupancy == 1.0
    while eng.active_count:
        eng.step()
    assert 0.0 < eng.slot_occupancy <= 1.0


# ---------------------------------------------------------------------------
# load generator
# ---------------------------------------------------------------------------


def test_percentile_empty_is_none():
    assert _percentile(np.asarray([]), 99) is None
    assert _percentile(np.asarray([5.0]), 99) == 5.0


def test_zipf_weights_shape():
    w = zipf_weights(100, 1.1)
    assert w.shape == (100,) and abs(w.sum() - 1.0) < 1e-12
    assert (np.diff(w) <= 0).all()  # rank-monotone
    assert np.allclose(zipf_weights(10, 0.0), 0.1)  # alpha=0 -> uniform


def test_make_workload_deterministic_and_skewed():
    cfg = LoadConfig(queries=200, qps=10.0, zipf_alpha=1.5, seed=4)
    q1, a1 = make_workload(1024, cfg)
    q2, a2 = make_workload(1024, cfg)
    assert [q.seeds for q in q1] == [q.seeds for q in q2]
    np.testing.assert_array_equal(a1, a2)
    assert a1[0] == 0.0 and (np.diff(a1) >= 0).all()
    # heavy-tailed: 200 draws over 1024 vertices reuse a small hot set
    single = [q.seeds[0] for q in q1 if len(q.seeds) == 1]
    assert len(set(single)) < len(single) / 2
    # different alpha -> different skew, same arrival seed stream structure
    q3, _ = make_workload(1024, LoadConfig(queries=200, qps=10.0,
                                           zipf_alpha=0.0, seed=4))
    assert len({q.seeds for q in q3}) > len({q.seeds for q in q1})


def test_closed_loop_saturates_and_sustains(g64):
    def run(qps, queries=30):
        vc = VirtualClock()
        rt = ServingRuntime(_engine(g64, slots=2), queue_depth=4,
                            clock=vc.now)
        qs, arr = make_workload(
            g64.n, LoadConfig(queries=queries, qps=qps, seed=0))
        return run_closed_loop(rt, qs, arr, clock=vc, step_cost_s=0.05)

    low = run(qps=1.0)
    assert low.rejected == 0
    assert low.completed == low.offered
    assert low.achieved_qps >= 0.9 * low.offered_qps
    high = run(qps=200.0)
    assert high.rejected > 0  # backpressure engaged
    assert high.completed + high.rejected + high.expired == high.offered
    assert high.achieved_qps < high.offered_qps
    assert high.queue_depth_max >= low.queue_depth_max


def test_closed_loop_midstream_updates(g64):
    from repro.core.dynamic import make_update_injector

    vc = VirtualClock()
    rt = ServingRuntime(_engine(g64), queue_depth=32, clock=vc.now)
    cfg = LoadConfig(queries=24, qps=50.0, repeat_fraction=0.5, seed=2)
    qs, arr = make_workload(g64.n, cfg)
    rep = run_closed_loop(
        rt, qs, arr, clock=vc, step_cost_s=0.01,
        update_injector=make_update_injector(np.random.default_rng(0), 8),
        update_at=(8, 16))
    assert rep.update_batches == 2
    assert rep.completed + rep.rejected + rep.expired == rep.offered == 24
    assert rep.completed > 0 and rep.p99_ms is not None
