"""The sharded build and solve of ``distributed_barrier`` at sizes the CPU
runs in seconds: the host partition against ``PartitionedGraph.from_graph``,
the two-step float32 sums, fixed-iteration Graphalytics runs against both
float64 references, the bundle's arrays as jit operands, and the benchmark's
generator, reference, readers and driver for the four-chip cell."""
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import kronecker_stream, shard_work, sharded_reference  # noqa: E402
from repro.core import PartitionedGraph, pagerank_numpy  # noqa: E402
from repro.core.distributed import CHUNK, _chunk_segments  # noqa: E402
from repro.core.pagerank import partition_edges  # noqa: E402
from repro.core.solver import get_variant  # noqa: E402
from repro.graphs.csr import EdgeList, Graph  # noqa: E402

GRAPH10 = {"scale": 10, "edgefactor": 16, "initiator": [0.57, 0.19, 0.19]}
SEED = 2**31 + 11  # the benchmark's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def kron10():
    n, src, dst = kronecker_stream.edges(GRAPH10, SEED)
    return n, src, dst, Graph.from_edges(n, src, dst)


@pytest.mark.parametrize("p", [1, 3, 4])
def test_partition_matches_from_graph(kron10, p):
    """Shard by shard, the sharded build's rows hold from_graph's edges:
    in from_graph's order when fed the graph's own (dst-sorted) order, and
    as the same (dst, src) multiset when fed a shuffled edge list."""
    n, _, _, g = kron10
    pg = PartitionedGraph.from_graph(g, p, gain=False)
    assert pg.gain is None
    want_src, want_local = np.asarray(pg.src_pad), np.asarray(pg.dst_local)
    counts = np.asarray(pg.emask).sum(axis=1).astype(int)
    shuffle = np.random.default_rng(0).permutation(g.m)
    for src, dst, ordered in ((g.src, g.dst, True),
                              (g.src[shuffle], g.dst[shuffle], False)):
        s, loc, c = partition_edges(n, src, dst, p)
        assert s.dtype == loc.dtype == np.int32 and c.dtype == np.int64
        assert s.shape == want_src.shape and (c == counts).all()
        for i, k in enumerate(counts):
            assert (loc[i, k:] == pg.vp).all() and (s[i, k:] == 0).all()
            assert (np.diff(loc[i, :k]) >= 0).all()
            got = np.lexsort((s[i, :k], loc[i, :k]))
            want = np.lexsort((want_src[i, :k], want_local[i, :k]))
            if ordered:
                assert (s[i, :k] == want_src[i, :k]).all()
            assert (s[i, :k][got] == want_src[i, :k][want]).all()
            assert (loc[i, :k][got] == want_local[i, :k][want]).all()


def test_partition_counts_past_int32():
    """Counts and offsets are int64: an edge list may hold 2**31 edges."""
    src = np.zeros(5, np.int32)
    dst = np.array([0, 9, 3, 9, 9], np.int32)
    s, loc, c = partition_edges(10, src, dst, 2)
    assert c.dtype == np.int64 and c.tolist() == [2, 3]
    assert loc[1].tolist() == [4, 4, 4] and loc[0].tolist() == [0, 3, 5]


def test_chunk_segments_bound_every_partial():
    """A segment never spans two destinations or more than CHUNK edges,
    and its destination is that of each of its edges."""
    local = np.array([[0] * (2 * CHUNK + 5) + [2, 2, 7] + [8] * 3], np.int32)
    counts = np.array([local.shape[1] - 3])
    local[0, counts[0]:] = 8  # padding
    rows = local.copy()
    seg_dst = _chunk_segments(rows, counts, 8)
    seg = rows[0, :counts[0]]
    assert (seg_dst[0][seg] == local[0, :counts[0]]).all()
    assert np.bincount(seg).max() <= CHUNK
    assert seg.tolist()[-3:] == [3, 3, 4]
    assert (rows[0, counts[0]:] == seg_dst.shape[1]).all()
    assert seg_dst[0, 5:].tolist() == [8] * (seg_dst.shape[1] - 5)


def test_fixed_iterations_match_both_references(kron10):
    """threshold=0 runs exactly max_iter rounds and lands on Graphalytics'
    ten iterations, in float64 by pagerank_numpy and by the bench's own
    reference."""
    n, src, dst, g = kron10
    v = get_variant("distributed_barrier")
    b = v.build(EdgeList(n, src, dst), threads=4)
    r = v.run(b, threshold=0.0, max_iter=10, handle_dangling=True)
    assert int(r.iterations) == 10
    oracle, _ = pagerank_numpy(g, threshold=0.0, max_iter=10,
                               handle_dangling=True)
    exact = sharded_reference.pagerank(n, src, dst, 0.85, 10)
    assert sharded_reference.compare(oracle, exact)[0] < 1e-12
    rel, l1 = sharded_reference.compare(np.asarray(r.pr), exact)
    assert rel < 5e-6 and l1 < 1e-5


def test_hub_partials_keep_float32_accurate():
    """A vertex with 2**21 in-edges: its two-step float32 sum stays within
    a few float32 roundings of float64 (one running float32 sum of that
    many terms drifts by about 1e-4)."""
    n, m = 1 << 14, 1 << 21
    rng = np.random.default_rng(3)
    src = rng.integers(1, n, m).astype(np.int32)
    dst = np.zeros(m, np.int32)
    extra = rng.integers(0, n, (2, 4 * n)).astype(np.int32)  # spread mass
    src, dst = np.r_[src, extra[0]], np.r_[dst, extra[1]]
    v = get_variant("distributed_barrier")
    r = v.run(v.build(EdgeList(n, src, dst), threads=1), threshold=0.0,
              max_iter=3, handle_dangling=True)
    exact = sharded_reference.pagerank(n, src, dst, 0.85, 3)
    got = np.asarray(r.pr, np.float64)
    assert abs(got[0] - exact[0]) / exact[0] < 2e-6


def test_bundle_arrays_are_jit_operands(kron10):
    """The bundle's jax.Array attributes are the compiled solve's operands:
    nothing the size of an edge array is embedded as a constant, and the
    compiled solve equals the eager one."""
    import dataclasses

    n, src, dst, _ = kron10
    v = get_variant("distributed_barrier")
    b = v.build(EdgeList(n, src, dst), threads=1)
    arrays = {k: x for k, x in vars(b).items() if isinstance(x, jax.Array)}
    assert set(arrays) == {"src", "seg", "seg_dst", "inv_out", "dangling"}
    opts = dict(threshold=0.0, max_iter=10, handle_dangling=True)
    lowered = jax.jit(lambda a: v.run(dataclasses.replace(b, **a), **opts)
                      ).lower(arrays)
    # no constant spelled out element by element beyond a few hundred
    sizes = re.findall(r'dense<(?:\[|")[^>]*>\s*:\s*tensor<(\d+)x',
                       lowered.as_text())
    assert all(int(k) < 256 for k in sizes), sizes
    got = lowered.compile()(arrays)
    want = v.run(b, **opts)
    assert int(got.iterations) == 10
    np.testing.assert_array_equal(np.asarray(got.pr), np.asarray(want.pr))


def test_generator_is_deterministic_per_seed():
    a = kronecker_stream.edges(GRAPH10, SEED)
    b = kronecker_stream.edges(GRAPH10, SEED)
    c = kronecker_stream.edges(GRAPH10, SEED + 1)
    assert a[0] == 1024 and a[1].size == 16 * 1024
    assert all((x == y).all() for x, y in zip(a[1:], b[1:]))
    assert not (a[1] == c[1]).all()
    assert a[1].dtype == np.int32 and 0 <= a[1].min() and a[1].max() < 1024


def test_generator_quadrants_follow_the_initiator():
    """Before the permutation, each level's (src bit, dst bit) falls in the
    initiator's quadrants A, B, C, D = 0.57, 0.19, 0.19, 0.05."""
    g = dict(GRAPH10, scale=12)
    _, src, dst = kronecker_stream.edges(g, SEED, permute=False)
    a, b = (src[:, None] >> np.arange(12)) & 1, (dst[:, None] >> np.arange(12)) & 1
    freq = [np.mean((a == i) & (b == j)) for i, j in ((0, 0), (0, 1), (1, 0), (1, 1))]
    np.testing.assert_allclose(freq, [0.57, 0.19, 0.19, 0.05], atol=0.003)


def test_collectives_found_by_op_kind():
    hlo = "%fusion.3 = f32[8]{0} all-gather-start(f32[2]{0} %x), dims={0}"
    assert shard_work.is_collective("all-gather-done.1")
    assert shard_work.is_collective("all-reduce.7")
    assert shard_work.is_collective("fusion.3", hlo)
    assert not shard_work.is_collective("fusion.4", "%fusion.4 = f32[8] add(%a, %b)")
    assert not shard_work.is_collective("scatter.2")
    ops = {"/device:TPU:0": [("fusion.1", "", 0, 40), ("all-gather.2", "", 30, 60),
                             ("all-reduce.3", "", 90, 100), ("gather.4", "", 150, 300)],
           "/device:TPU:3": [("scatter.1", "", 100, 150)]}
    t = shard_work.chip_times(ops, (10, 200))
    assert t[0].busy_s == pytest.approx(1e-9 * (50 + 10 + 50))
    assert t[0].collective_s == pytest.approx(1e-9 * (30 + 10))
    assert t[3].busy_s == pytest.approx(50e-9) and t[3].collective_s == 0


def _spec(scale=9, **mix):
    from bench.harness import cell_spec

    spec = cell_spec("graph500-27.dist-barrier10")
    spec["config"]["graph"]["scale"] = scale
    spec["config"]["chips"] = jax.device_count()
    spec["mix"].update(mix)
    return spec


def _drive(spec):
    from bench.harness import run_cell

    return run_cell(spec, SEED, 0.5, False, "TPU v5 lite")


def test_driver_sound_run_is_correct():
    run, out = _drive(_spec())
    assert out.correct and out.failed == 0 and out.attempted >= 1
    assert set(out.end_to_end) == {"solve_s"}
    assert run.facts["compiles_in_window"] == 0
    assert run.facts["rounds"] == 10 * run.facts["solves"]
    checks = {name: v for name, v, _ in out.checks}
    assert checks["rel_max"] < 1e-5 and checks["rounds_off"] == 0


def test_driver_refuses_a_dropped_shard(monkeypatch):
    """The build loses shard 0's edges (its count set to zero)."""
    import repro.core.distributed as dist

    real = dist.partition_edges

    def drop_first(*a, **k):
        src, local, counts = real(*a, **k)
        counts[0] = 0
        return src, local, counts

    monkeypatch.setattr(dist, "partition_edges", drop_first)
    _, out = _drive(_spec())
    assert not out.correct
    assert dict((n, v) for n, v, _ in out.checks)["rel_max"] > 1e-2


def test_driver_refuses_the_bfloat16_control():
    _, out = _drive(_spec(precision="bfloat16"))
    assert not out.correct
    assert dict((n, v) for n, v, _ in out.checks)["rel_max"] > 1e-3


def test_shard_readers(monkeypatch):
    """The per-layer readers on hand-made chip times and layout."""
    import types

    from bench.harness import reader
    from repro.utils import tracing

    lay = {"p": 2, "vp": 4, "n_pad": 8, "cap": 10,
           "edges_per_shard": [9, 3], "exchange_bytes_per_round": 16}
    monkeypatch.setitem(tracing.LAYOUTS, "distributed", lay)
    chips = {5: shard_work.ChipTime(busy_s=4.0, collective_s=1.0),
             7: shard_work.ChipTime(busy_s=2.0, collective_s=1.0)}
    monkeypatch.setattr(shard_work, "of_run", lambda run: chips)
    run = types.SimpleNamespace(facts={"rounds": 2, "shard_devices": [7, 5]},
                                device_kind="TPU v5 lite")
    assert reader("round_ms.dist").read(run, None) == pytest.approx(1500.0)
    assert reader("exchange_share.dist").read(run, None) == pytest.approx(100 / 3)
    roof = reader("shard_roofline.dist").read(run, None)
    bw = 819e9
    want5 = 100 * shard_work.round_bytes(8, 4, 3) / bw / 1.5
    want7 = 100 * shard_work.round_bytes(8, 4, 9) / bw / 0.5
    assert roof["per_chip"] == pytest.approx({"5": want5, "7": want7})
    assert reader("shard_imbalance.dist").read(run, None)["value"] == 1.5
    monkeypatch.delitem(tracing.LAYOUTS, "distributed")
    assert reader("shard_imbalance.dist").read(run, None) is None
    assert reader("round_ms.dist").read(run, None) is None


_FOUR = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "src")]
    import numpy as np, jax
    from bench import kronecker_stream, sharded_reference
    from bench.harness import cell_spec, run_cell
    from repro.core import pagerank_numpy
    from repro.core.solver import get_variant
    from repro.graphs.csr import EdgeList, Graph
    import repro.core.distributed as dist

    g = {"scale": 10, "edgefactor": 16, "initiator": [0.57, 0.19, 0.19]}
    n, src, dst = kronecker_stream.edges(g, 2**31 + 11, jax.devices())
    v = get_variant("distributed_barrier")
    b = v.build(EdgeList(n, src, dst), threads=4)
    r = v.run(b, threshold=0.0, max_iter=10, handle_dangling=True)
    oracle, _ = pagerank_numpy(Graph.from_edges(n, src, dst), threshold=0.0,
                               max_iter=10, handle_dangling=True)
    exact = sharded_reference.pagerank(n, src, dst, 0.85, 10)
    out = {"p": b.p, "rounds": int(r.iterations),
           "vs_numpy": sharded_reference.compare(np.asarray(r.pr), oracle)[0],
           "vs_bench": sharded_reference.compare(np.asarray(r.pr), exact)[0],
           "shards": {k: sorted((s.device.id, s.data.shape)
                                for s in getattr(b, k).addressable_shards)
                      for k in ("src", "seg", "seg_dst")},
           "cap": b.cap, "n_seg": b.seg_dst.shape[0] // b.p,
           "devices": sorted(d.id for d in b.mesh.devices.flat)}
    spec = cell_spec("graph500-27.dist-barrier10")
    spec["config"]["graph"]["scale"] = 10
    out["sound"] = run_cell(spec, 2**31 + 11, 0.5, False, "TPU v5 lite")[1].correct
    real = dist.partition_edges
    def drop(*a, **k):
        s, loc, c = real(*a, **k)
        c[2] = 0
        return s, loc, c
    dist.partition_edges = drop
    out["dropped"] = run_cell(spec, 2**31 + 11, 0.5, False, "TPU v5 lite")[1].correct
    print(json.dumps(out))
""")


@pytest.mark.slow
@pytest.mark.subprocess
def test_four_host_devices():
    """On four forced host devices: ten Graphalytics rounds match both
    float64 references, each device holds exactly its shard's rows, and the
    benchmark's driver gives correct true, and false with one shard of four
    dropped."""
    res = subprocess.run([sys.executable, "-c", _FOUR], capture_output=True,
                         text=True, cwd=ROOT, timeout=600,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["p"] == 4 and out["rounds"] == 10
    assert out["vs_numpy"] < 5e-6 and out["vs_bench"] < 5e-6
    for k, width in (("src", out["cap"]), ("seg", out["cap"]),
                     ("seg_dst", out["n_seg"])):
        assert out["shards"][k] == [[d, [width]] for d in out["devices"]], k
    assert out["sound"] is True and out["dropped"] is False
