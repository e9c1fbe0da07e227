"""Distributed behaviour: distributed PageRank (multi host-device
subprocess) and the fault simulation."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import FaultPlan, PartitionedGraph, l1_norm, pagerank_numpy, simulate
from repro.graphs import rmat_graph


# ---------------------------------------------------------------------------
# distributed PageRank on 8 host devices (subprocess so XLA_FLAGS applies)
# ---------------------------------------------------------------------------


_DIST_SCRIPT = textwrap.dedent(
    """
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.graphs import rmat_graph
    from repro.core import PartitionedGraph, distributed_pagerank, pagerank_numpy, l1_norm
    from repro.core.solver import build_variant, bundle_partitions, solve_variant

    g = rmat_graph(9, avg_degree=6, seed=1)
    ref, _ = pagerank_numpy(g, threshold=1e-12)
    pg = PartitionedGraph.from_graph(g, p=8)
    from jax.sharding import AxisType
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    out = {}
    rb = distributed_pagerank(pg, mesh, mode="barrier", threshold=1e-7)
    out["barrier"] = {"rounds": int(rb.iterations), "l1": l1_norm(rb.pr, ref)}
    rs = distributed_pagerank(pg, mesh, mode="stale", local_sweeps=4, threshold=1e-7)
    out["stale"] = {"rounds": int(rs.iterations), "l1": l1_norm(rs.pr, ref)}

    # registry path: the three distributed entries converge to the oracle's
    # DANGLING-redistributed fixed point (the bug this PR fixes: the solvers
    # used to silently drop handle_dangling) on a genuinely 8-way mesh
    ref_d, _ = pagerank_numpy(g, threshold=1e-12, handle_dangling=True)
    _, bundle = build_variant("distributed_stale", g, threads=8)
    out["bundle_p"] = bundle_partitions(bundle)
    for vname in ("distributed_barrier", "distributed_stale", "distributed_topk"):
        r = solve_variant(vname, g, threshold=1e-8, handle_dangling=True,
                          threads=8, local_sweeps=4)
        out[vname] = {"rounds": int(r.iterations), "l1": l1_norm(r.pr, ref_d)}
    print(json.dumps(out))
    """
)


@pytest.mark.slow
@pytest.mark.subprocess
def test_distributed_pagerank_8way():
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["barrier"]["l1"] < 1e-3
    assert out["stale"]["l1"] < 1e-3
    # the stale (no-sync) schedule must not need more exchanges than barrier
    assert out["stale"]["rounds"] <= out["barrier"]["rounds"]
    # registry build really sharded 8 ways (not a degenerate p=1 fallback)
    assert out["bundle_p"] == 8
    # dangling-mass parity (acceptance: L1 < 1e-5 at threshold 1e-8)
    for vname in ("distributed_barrier", "distributed_stale", "distributed_topk"):
        assert out[vname]["l1"] < 1e-5, vname


# ---------------------------------------------------------------------------
# fault-tolerance simulation (paper Fig 8/9)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pg():
    return PartitionedGraph.from_graph(rmat_graph(8, avg_degree=5, seed=7), p=4)


def test_sim_all_disciplines_converge_clean(pg):
    for d in ("barrier", "nosync", "waitfree"):
        r = simulate(pg, d, threshold=1e-8)
        assert r.iterations < 1000, d


def test_sim_sleep_hurts_barrier_not_waitfree(pg):
    """Fig 8: barrier time grows with injected sleep; wait-free stays flat."""
    sleep = {(0, it): 5.0 for it in range(1, 200)}
    base_b = simulate(pg, "barrier", threshold=1e-8).sim_time
    slow_b = simulate(pg, "barrier", FaultPlan(sleeps=sleep), threshold=1e-8).sim_time
    slow_w = simulate(pg, "waitfree", FaultPlan(sleeps=sleep), threshold=1e-8).sim_time
    assert slow_b > base_b * 3
    assert slow_w < slow_b  # helping absorbs the sleeping partition
    # nosync: sleeping thread only delays its own partition
    slow_n = simulate(pg, "nosync", FaultPlan(sleeps=sleep), threshold=1e-8).sim_time
    assert slow_n <= slow_b


def test_sim_failure_only_waitfree_survives(pg):
    """Fig 9: with a failed thread, wait-free completes; barrier does not."""
    plan = FaultPlan(failures={1: 2})
    rw = simulate(pg, "waitfree", plan, threshold=1e-8)
    assert rw.iterations < 1000
    ref, _ = pagerank_numpy(rmat_graph(8, avg_degree=5, seed=7), threshold=1e-12)
    assert l1_norm(rw.pr, ref) < 1e-2
    rb = simulate(pg, "barrier", plan, threshold=1e-8, max_iter=50)
    assert rb.iterations == 50  # never converges


def test_sim_waitfree_work_stealing(pg):
    """Helpers adopt the failed worker's partition (paper's helping)."""
    plan = FaultPlan(failures={0: 1})
    r = simulate(pg, "waitfree", plan, threshold=1e-8)
    assert r.work_done[0] == 0 or r.work_done[0] < r.iterations
    total = sum(r.work_done.values())
    assert total >= r.iterations * pg.p  # every partition swept every round


# ---------------------------------------------------------------------------
# static-allocation load skew: edge-balanced boundaries in the cost model
# ---------------------------------------------------------------------------


def test_edge_balanced_boundaries_fix_load_skew():
    """`Graph.partition_ranges(edge_balanced=True)` really equalizes per-
    partition edge loads on a hub-heavy graph, and the runtime cost model
    (simulate_jittered with rel_costs) turns that into a better barrier
    makespan — the load-skew fix the docstring promises."""
    from repro.core import partition_sweep_costs, simulate_jittered
    from repro.graphs.csr import Graph

    # hub-heavy: 90% of edges land on the first 16 of 256 vertices, so
    # equal-vertex splits give partition 0 almost all the work
    rng = np.random.default_rng(0)
    m = 4000
    src = rng.integers(0, 256, m)
    dst = np.where(rng.random(m) < 0.9,
                   rng.integers(0, 16, m), rng.integers(0, 256, m))
    g = Graph.from_edges(256, src, dst)
    p = 8

    ev = partition_sweep_costs(g, p, edge_balanced=False)
    eb = partition_sweep_costs(g, p, edge_balanced=True)
    assert ev.sum() == eb.sum() == g.m  # both cover every edge exactly once
    skew_ev = ev.max() / ev.mean()
    skew_eb = eb.max() / eb.mean()
    assert skew_ev > 3.0  # equal-vertex really is skewed here
    assert skew_eb < skew_ev / 2  # edge-balanced removes most of it

    pg = PartitionedGraph.from_graph(g, p=p)
    t_ev = simulate_jittered(pg, "barrier", iterations=50, seed=3, rel_costs=ev)
    t_eb = simulate_jittered(pg, "barrier", iterations=50, seed=3, rel_costs=eb)
    assert t_eb < t_ev  # the barrier waits on the hub partition

    with pytest.raises(ValueError, match="rel_costs"):
        simulate_jittered(pg, "barrier", iterations=5, rel_costs=ev[:-1])
