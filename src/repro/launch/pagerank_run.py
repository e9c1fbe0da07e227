"""PageRank driver: run any registered variant on any Table-1 dataset surrogate.

    PYTHONPATH=src python -m repro.launch.pagerank_run --dataset webStanford \
        --variant nosync --threads 56 [--scale-down 256] [--ckpt /tmp/pr]

Variants come from the registry (``repro.core.solver``); ``--list`` prints
them with their ``layout``/``backend``/``schedule`` metadata columns.  The
Pallas variants compile for the TPU, and run in the Pallas interpreter on the
CPU backend (``repro.utils.platform.pallas_interpret``).  Compiled programs
are cached across runs (``repro.utils.platform.init_compile_cache``).

Two subcommands expose the personalized-PageRank subsystem:

    # one-shot PPR query (push solver by default)
    ... -m repro.launch.pagerank_run query --dataset webStanford \
        --seeds 7,42 --top-k 10

    # continuous-batching PPR serving demo over random seed queries
    ... -m repro.launch.pagerank_run serve --dataset webStanford \
        --slots 8 --queries 32

The ``build`` subcommand runs the out-of-core pipeline (generate → reorder →
layout, resumable; see docs/STORAGE.md) and the main solve path accepts the
result via ``--store``:

    ... -m repro.launch.pagerank_run build --out /tmp/g22 --scale 22
    ... -m repro.launch.pagerank_run --store /tmp/g22 --variant nosync

A killed ``build`` resumes from its last completed chunk; ``--store`` loads
the graph memmap-backed and un-permutes ranks to original vertex ids before
printing or checkpointing.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.core import SolverCheckpoint, l1_norm, pagerank_numpy
from repro.core.solver import (
    build_variant, bundle_partitions, get_variant, list_variants, plan_stats,
)
from repro.graphs import DATASETS, make_dataset
from repro.utils.platform import init_compile_cache


def _parse_seeds(spec: str) -> tuple[int, ...]:
    """``"7,42"`` → ``(7, 42)``; empty string → uniform (global) teleport."""
    return tuple(int(s) for s in spec.split(",") if s.strip() != "")


def query_main(argv) -> int:
    ap = argparse.ArgumentParser(prog="pagerank_run query")
    ap.add_argument("--dataset", choices=tuple(DATASETS), default="webStanford")
    ap.add_argument("--scale-down", type=float, default=256.0)
    ap.add_argument("--seeds", default="", help="comma-separated seed vertices"
                    " (empty = uniform teleport, i.e. global PageRank)")
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--solver", choices=("push", "batched"), default="push")
    ap.add_argument("--threshold", type=float, default=1e-8,
                    help="push residual bound rmax / engine threshold")
    ap.add_argument("--handle-dangling", action="store_true")
    args = ap.parse_args(argv)

    from repro.ppr import ppr_push, teleport_from_seeds, topk
    from repro.ppr.batched import ppr_barrier
    from repro.core.pagerank import DeviceGraph

    g = make_dataset(args.dataset, scale_down=args.scale_down)
    seeds = _parse_seeds(args.seeds)
    print(f"{args.dataset}: n={g.n} m={g.m}  seeds={list(seeds) or 'uniform'}")
    t0 = time.time()
    if args.solver == "push":
        res = ppr_push(g, seeds, rmax=args.threshold,
                       handle_dangling=args.handle_dangling)
        idx, vals = res.topk(args.top_k)
        extra = (f"rounds={res.rounds} pushes={res.pushes} "
                 f"l1_bound={res.l1_bound:.2e}")
    else:
        r = ppr_barrier(DeviceGraph.from_graph(g),
                        teleport_from_seeds([seeds], g.n),
                        threshold=args.threshold,
                        handle_dangling=args.handle_dangling)
        idx, vals = topk(np.asarray(r.pr, np.float64)[0], args.top_k)
        extra = f"iterations={int(r.iterations)} err={float(r.err):.2e}"
    wall = time.time() - t0
    print(f"solver={args.solver}: {extra} wall={wall:.3f}s")
    for rank, (v, x) in enumerate(zip(idx, vals), 1):
        print(f"  #{rank:<3d} vertex {int(v):<8d} ppr={float(x):.6e}")
    return 0


def serve_main(argv) -> int:
    ap = argparse.ArgumentParser(prog="pagerank_run serve")
    ap.add_argument("--dataset", choices=tuple(DATASETS), default="webStanford")
    ap.add_argument("--scale-down", type=float, default=256.0)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--threshold", type=float, default=1e-6)
    ap.add_argument("--backend", choices=("jax", "pallas"), default="jax")
    ap.add_argument("--handle-dangling", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="offered load: drive the serving runtime with a "
                         "target-qps Zipf-skewed closed loop instead of the "
                         "all-at-once drain (docs/SERVING.md)")
    ap.add_argument("--queue-depth", type=int, default=32,
                    help="admission-queue bound; a full queue rejects "
                         "(backpressure)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-query queue-wait deadline; expired queries are "
                         "dropped, never solved (0 = none)")
    ap.add_argument("--mesh-shards", type=int, default=0,
                    help="shard the (B, n) slot batch over this many devices "
                         "(1-D serving mesh; 0 = unsharded). slots must "
                         "divide evenly")
    ap.add_argument("--zipf-alpha", type=float, default=1.1,
                    help="seed-popularity skew of the --qps workload")
    ap.add_argument("--updates", type=int, default=0, metavar="N",
                    help="apply N random edge updates (adds+dels) mid-stream "
                         "— the dynamic-graph serving path (docs/DYNAMIC.md); "
                         "the runtime quiesces, swaps the backend, and "
                         "invalidates stale cached answers by dst block")
    ap.add_argument("--update-batches", type=int, default=1,
                    help="split --updates over this many batches")
    ap.add_argument("--localized", action="store_true",
                    help="sink-bounded updates (dangling→dangling adds) "
                         "instead of uniform random ones")
    args = ap.parse_args(argv)
    if args.queries < 1:
        ap.error("--queries must be >= 1")

    from repro.serving.ppr_engine import PPREngine, make_query_stream
    from repro.serving.runtime import ServingRuntime

    g = make_dataset(args.dataset, scale_down=args.scale_down)
    mesh = None
    if args.mesh_shards > 0:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh_shards)
    shards = mesh.devices.size if mesh is not None else 1
    print(f"{args.dataset}: n={g.n} m={g.m}  slots={args.slots} "
          f"backend={args.backend} mesh_shards={shards}")
    eng = PPREngine(g, slots=args.slots, threshold=args.threshold,
                    backend=args.backend, mesh=mesh,
                    handle_dangling=args.handle_dangling)
    runtime = ServingRuntime(
        eng, queue_depth=args.queue_depth,
        deadline_s=args.deadline_ms * 1e-3 if args.deadline_ms > 0 else None)

    n_batches = max(args.update_batches, 1)
    per_batch = max(1, args.updates // n_batches) if args.updates else 0
    if args.qps > 0:
        from repro.serving.loadgen import (
            LoadConfig, make_workload, run_closed_loop,
        )

        cfg = LoadConfig(queries=args.queries, qps=args.qps,
                         top_k=args.top_k, zipf_alpha=args.zipf_alpha,
                         seed=args.seed)
        queries, arrivals = make_workload(g.n, cfg)
        kwargs = {}
        if args.updates > 0:
            from repro.core.dynamic import make_update_injector

            step = max(1, args.queries // (n_batches + 1))
            kwargs = dict(
                update_injector=make_update_injector(
                    np.random.default_rng(args.seed), per_batch,
                    localized=args.localized),
                update_at=tuple(step * (i + 1) for i in range(n_batches)))
        rep = run_closed_loop(runtime, queries, arrivals, **kwargs)
        p50 = f"{rep.p50_ms:.1f}ms" if rep.p50_ms is not None else "n/a"
        p99 = f"{rep.p99_ms:.1f}ms" if rep.p99_ms is not None else "n/a"
        print(f"offered {rep.offered_qps:.1f} q/s → achieved "
              f"{rep.achieved_qps:.1f} q/s  p50={p50} p99={p99} (under load)")
        print(f"queue depth mean={rep.queue_depth_mean:.1f} "
              f"max={rep.queue_depth_max:.0f}  "
              f"rejected={rep.rejected} ({rep.rejection_rate:.1%})  "
              f"expired={rep.expired}  cache_hits={rep.cache_hits}  "
              f"invalidations={rep.cache_invalidations}")
    else:
        queries = make_query_stream(g.n, args.queries, top_k=args.top_k,
                                    seed=args.seed)
        t0 = time.time()
        if args.updates > 0:
            from repro.core.dynamic import random_update_batch

            half = len(queries) // 2
            responses = runtime.serve(queries[:half])
            rng = np.random.default_rng(args.seed)
            applied = 0
            for _ in range(n_batches):
                adds, dels = random_update_batch(eng.g, rng, per_batch,
                                                 localized=args.localized)
                delta, drained = runtime.apply_updates(adds=adds, dels=dels)
                responses += drained
                applied += delta.num_ops
            print(f"applied {applied} edge updates "
                  f"({'localized' if args.localized else 'random'}, "
                  f"{n_batches} batch(es)): n={eng.g.n} m={eng.g.m}, "
                  f"warm cache now {len(eng._cache)} rows, result cache "
                  f"{runtime.result_cache_len} "
                  f"(invalidated "
                  f"{runtime.metrics.count('cache_invalidations')})")
            responses += runtime.serve(queries[half:])
        else:
            responses = runtime.serve(queries)
        wall = time.time() - t0
        lat = np.asarray([r.latency_s for r in responses]) * 1e3
        print(f"served {len(responses)} queries in {wall:.2f}s "
              f"({len(responses) / wall:.1f} q/s)  "
              f"p50={np.percentile(lat, 50):.1f}ms "
              f"p99={np.percentile(lat, 99):.1f}ms  warm_hits={eng.warm_hits}"
              f"  cache_hits={runtime.metrics.count('cache_hits')}")
        first = min(responses, key=lambda r: r.qid)
        top = ", ".join(f"{int(v)}:{float(x):.2e}"
                        for v, x in zip(first.indices[:5], first.values[:5]))
        print(f"sample qid={first.qid} seeds={list(first.seeds)} top5: {top}")
    # backpressure/occupancy observability: queries bounced off a full batch
    # used to vanish silently — the summary now always surfaces them
    print(f"slots: occupancy={eng.slot_occupancy:.0%} "
          f"submit_rejections={eng.submit_rejections} "
          f"(re-queued, not dropped)  {runtime.metrics.summary()}")
    if eng.layout is not None:
        lay = eng.layout
        print(f"layout: block={lay.block} tile_cap={lay.tile_cap} "
              f"tiles={lay.tiles} fill={lay.fill:.3f} "
              f"({'chosen' if lay.chosen else 'passed'})")
    return 0


def build_main(argv) -> int:
    ap = argparse.ArgumentParser(prog="pagerank_run build")
    ap.add_argument("--out", required=True,
                    help="pipeline directory (PIPELINE.json + raw/ + "
                         "reordered/ stores); rerun with the same --out to "
                         "resume an interrupted build")
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--scale", type=int, default=None,
                     help="R-MAT scale: 2**scale vertices")
    src.add_argument("--dataset", choices=tuple(DATASETS), default=None,
                     help="build a Table-1 surrogate instead of a pure R-MAT")
    ap.add_argument("--scale-down", type=float, default=1.0,
                    help="dataset surrogate scale-down (with --dataset)")
    ap.add_argument("--avg-degree", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk-edges", type=int, default=1 << 21,
                    help="edges per streamed chunk — the peak-memory knob")
    ap.add_argument("--order", choices=("none", "bfs", "degree", "random"),
                    default="bfs")
    ap.add_argument("--no-dedupe", action="store_true",
                    help="keep duplicate edges (R-MAT builds dedupe by "
                         "default, dataset surrogates never do)")
    ap.add_argument("--threads", type=int, default=56)
    ap.add_argument("--block", type=int, default=256)
    ap.add_argument("--tile-cap", type=int, default=1024)
    ap.add_argument("--stages", default=None,
                    help="comma-separated subset of generate,reorder,layout "
                         "(default: all)")
    args = ap.parse_args(argv)
    if args.scale is None and args.dataset is None:
        ap.error("one of --scale / --dataset is required")

    import math

    from repro.graphs.datasets import _dataset_rmat_params
    from repro.graphs.pipeline import BuildConfig, run_pipeline
    from repro.graphs.store import GraphStore

    if args.dataset is not None:
        n, m, (a, b, c) = _dataset_rmat_params(args.dataset, args.scale_down)
        cfg = BuildConfig(
            scale=max(6, math.ceil(math.log2(n))), n_edges=m, fold_n=n,
            a=a, b=b, c=c, seed=args.seed, dedupe=False,
            chunk_edges=args.chunk_edges, order=args.order,
            threads=args.threads, block=args.block, tile_cap=args.tile_cap)
    else:
        cfg = BuildConfig(
            scale=args.scale, avg_degree=args.avg_degree, seed=args.seed,
            dedupe=not args.no_dedupe, chunk_edges=args.chunk_edges,
            order=args.order, threads=args.threads, block=args.block,
            tile_cap=args.tile_cap)
    stages = args.stages.split(",") if args.stages else None
    res = run_pipeline(args.out, cfg, stages=stages)
    store = GraphStore(res["store"])
    print(f"store: {store.path}  n={store.n} m={store.m} "
          f"order={store.meta.get('order')} "
          f"bytes={store.nbytes():,}")
    lay = store.layout()
    if lay:
        ts = lay["tile_stats"]
        print(f"layout: threads={lay['threads']} tiles={ts['n_tiles']} "
              f"occupancy={ts['occupancy']:.3f}")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    init_compile_cache()
    if argv and argv[0] == "query":
        return query_main(argv[1:])
    if argv and argv[0] == "serve":
        return serve_main(argv[1:])
    if argv and argv[0] == "build":
        return build_main(argv[1:])
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", choices=tuple(DATASETS), default="webStanford")
    ap.add_argument("--scale-down", type=float, default=256.0)
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="solve a `build` pipeline directory (or a bare store "
                         "directory) memmap-backed instead of --dataset; "
                         "ranks are un-permuted to original vertex ids")
    ap.add_argument("--variant", choices=list_variants(), default="nosync")
    ap.add_argument("--threads", type=int, default=56)
    ap.add_argument("--threshold", type=float, default=1e-8)
    ap.add_argument("--block", type=int, default=256, help="pallas dst/src block size")
    ap.add_argument("--tile-cap", type=int, default=1024, help="pallas edges per tile")
    ap.add_argument("--local-sweeps", type=int, default=4,
                    help="distributed: GS sweeps per exchange (staleness bound)")
    ap.add_argument("--send-fraction", type=float, default=0.125,
                    help="distributed_topk: fraction of deltas published per round")
    ap.add_argument("--handle-dangling", action="store_true",
                    help="redistribute dangling mass uniformly (all variants)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--list", action="store_true",
                    help="list every registered variant and exit; columns are "
                         "the registry metadata triple the generic drivers "
                         "dispatch on — layout (bundle-sharing key: variants "
                         "with the same layout share one build), backend "
                         "(numpy | jax | pallas | shard_map; pallas runs "
                         "interpreted on the CPU backend), schedule (barrier | nosync | "
                         "sequential: the cost-model discipline)")
    args = ap.parse_args(argv)

    if args.list:
        # print the full metadata triple the registry carries — the drivers
        # dispatch on it, so the operator should see it too — plus the
        # static contract audit's verdict per variant (✓, or the failed
        # check keys; see docs/ANALYSIS.md)
        from repro.analysis.contracts import audit_registry

        audit = audit_registry()
        header = (f"{'variant':20s} {'layout':18s} {'backend':10s} "
                  f"{'schedule':10s} {'contract':10s} description")
        print(header)
        print("-" * len(header))
        for name in list_variants():
            v = get_variant(name)
            flags = ",".join(sorted({f.check for f in audit[name]})) or "✓"
            print(f"{name:20s} {v.layout:18s} {v.backend:10s} {v.schedule:10s} "
                  f"{flags:10s} {v.description}")
        return 0

    perm = None
    if args.store:
        from repro.graphs.store import GraphStore, is_store
        from repro.graphs.pipeline import final_store_path

        path = args.store if is_store(args.store) \
            else final_store_path(args.store)
        store = GraphStore(path)
        g = store.graph(mmap=True)
        perm = store.perm()
        print(f"store {store.path}: n={g.n} m={g.m} "
              f"order={store.meta.get('order')} (memmap)")
    else:
        g = make_dataset(args.dataset, scale_down=args.scale_down)
        print(f"{args.dataset}: n={g.n} m={g.m} "
              f"(scale_down={args.scale_down:g})")
    ref, it_seq = pagerank_numpy(g, threshold=1e-12,
                                 handle_dangling=args.handle_dangling)

    opts = dict(
        threads=args.threads,
        block=args.block,
        tile_cap=args.tile_cap,
        local_sweeps=args.local_sweeps,
        send_fraction=args.send_fraction,
    )
    t0 = time.time()
    v, bundle = build_variant(args.variant, g, **opts)
    ps = plan_stats(bundle)
    if ps:
        print(f"plan: core n={ps['core_n']} m={ps['core_m']} "
              f"(pruned identical={ps['pruned_identical']} "
              f"chain={ps['pruned_chain']} dead={ps['pruned_dead']}; "
              f"edges pruned={ps['pruned_edges']} "
              f"contracted={ps['contracted_edges']})")
    r = v.run(bundle, threshold=args.threshold,
              handle_dangling=args.handle_dangling, **opts)
    pr, iters, err = np.asarray(r.pr), int(r.iterations), float(r.err)
    if pr.ndim == 2:
        # ppr_* variants return a (b, n) batch; this driver passes no seeds,
        # so b == 1 and the single row is the uniform-teleport (global)
        # solve — flatten it for the L1/top-5/checkpoint paths below
        assert pr.shape[0] == 1, pr.shape
        pr = pr[0]
    wall = time.time() - t0

    if perm is not None:
        # a reordered store solves in stored order; report in ORIGINAL ids
        from repro.graphs.reorder import unpermute_ranks

        pr, ref = unpermute_ranks(pr, perm), unpermute_ranks(ref, perm)
    print(f"variant={args.variant}: iterations={iters} err={err:.2e} wall={wall:.2f}s")
    print(f"L1 vs sequential(1e-12, {it_seq} iters): {l1_norm(pr, ref):.3e}")
    print(f"top-5 ranks: {np.argsort(pr)[::-1][:5].tolist()}")
    if args.ckpt:
        # record the partition count actually baked into the bundle (1 for
        # unpartitioned variants) — NOT --threads: reshard-on-load must not
        # assume a partition layout the solve never used
        SolverCheckpoint(pr=pr, round=iters, n=g.n,
                         p=bundle_partitions(bundle)).save(args.ckpt)
        print(f"checkpointed to {args.ckpt}.npz (p={bundle_partitions(bundle)})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
