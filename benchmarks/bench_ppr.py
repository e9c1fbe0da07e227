"""PPR serving benchmark: one-shot drain + closed-loop latency under load.

Two measurement modes over the continuous-batching PPR engine
(`repro.serving.ppr_engine`) and the serving runtime
(`repro.serving.runtime`):

* **oneshot** — the original drain measurement: every query already
  waiting, queries/sec + p50/p99 submit→harvest latency.  Zero queueing,
  so it bounds the service rate, not the behavior under load.
* **closed loop** (``--load``) — a target-qps arrival process with
  Zipfian seed skew (`repro.serving.loadgen`) drives the admission queue
  at each offered rate in ``--qps``; each record reports achieved qps,
  p50/p99 *under load* (queue wait included), queue-depth stats, and the
  rejection rate, and the sweep reports ``saturation_qps`` — the highest
  sustained rate.

    PYTHONPATH=src python -m benchmarks.bench_ppr --scale 9 --queries 64 \
        --load --qps 8,32,128 --backends jax,pallas --json BENCH_ppr.json

``--json`` writes the ``BENCH_ppr.json`` artifact (check.sh emits it next
to ``BENCH_variants.json``): ``oneshot`` records plus ``closed_loop``
records and per-backend ``saturation_qps``.  Every record carries its
``backend``/``slots``/graph metadata so records from different sweeps are
self-describing, and percentile fields are ``None`` (not a crash) when a
saturated run completes nothing.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.graphs import rmat_graph
from repro.serving.loadgen import (
    LoadConfig, _percentile, make_workload, run_closed_loop,
)
from repro.serving.ppr_engine import PPREngine, make_query_stream
from repro.serving.runtime import ServingRuntime


def bench(scale: int = 9, avg_degree: int = 8, queries: int = 64,
          slots: int = 8, threshold: float = 1e-6, backend: str = "jax",
          iters_per_step: int = 8, top_k: int = 10, seed: int = 0) -> dict:
    """One-shot drain record (queries/sec + submit→harvest percentiles)."""
    if queries < 1:
        raise ValueError("bench_ppr needs at least one query "
                         "(percentiles of an empty stream are undefined)")
    g = rmat_graph(scale, avg_degree=avg_degree, seed=seed)
    eng = PPREngine(g, slots=slots, threshold=threshold, backend=backend,
                    iters_per_step=iters_per_step)
    qs = make_query_stream(g.n, queries, top_k=top_k, seed=seed)
    # warmup traces/compiles the jitted batched step; the measured run then
    # REUSES this engine (a fresh engine would re-jit inside the timed
    # region) with the warm cache cleared so the measurement starts cold
    eng.drain(qs[:min(2, len(qs))])
    eng.reset()
    t0 = time.perf_counter()
    responses = eng.drain(qs)
    wall = time.perf_counter() - t0
    lat_ms = np.asarray([r.latency_s for r in responses]) * 1e3
    iters = np.asarray([r.iterations for r in responses])
    return {
        "mode": "oneshot",
        "n": g.n,
        "m": g.m,
        "backend": backend,
        "slots": slots,
        "threshold": threshold,
        "iters_per_step": iters_per_step,
        "queries": len(responses),
        "wall_s": wall,
        "qps": len(responses) / wall,
        "p50_ms": _percentile(lat_ms, 50),
        "p99_ms": _percentile(lat_ms, 99),
        "mean_iters": float(iters.mean()) if iters.size else None,
        "warm_hits": eng.warm_hits,
        "slot_occupancy": eng.slot_occupancy,
    }


def bench_load(scale: int = 9, avg_degree: int = 8, queries: int = 64,
               slots: int = 8, threshold: float = 1e-6, backend: str = "jax",
               iters_per_step: int = 8, top_k: int = 10, seed: int = 0,
               qps_list=(8.0, 32.0, 128.0), queue_depth: int = 32,
               deadline_ms: float = 0.0, zipf_alpha: float = 1.1,
               updates: int = 0) -> tuple[list[dict], float | None]:
    """Offered-qps sweep: per-rate closed-loop records + saturation qps.

    One engine serves the whole sweep (its jitted step is traced once,
    outside every measured window); each rate starts from a reset runtime
    so queues, caches, and metrics are cold.  ``updates > 0`` injects that
    many random edge updates mid-stream at every rate — measuring latency
    under load *with* result-cache invalidation churn.  Updates mutate the
    engine's graph permanently, so after an updating rate the engine is
    rebuilt from the pristine graph (and re-warmed outside the measured
    window): every rate in the sweep measures the SAME graph, and each
    record carries ``m_final`` to show the within-run edge drift."""
    g = rmat_graph(scale, avg_degree=avg_degree, seed=seed)
    warm_qs = make_query_stream(g.n, min(2, queries), top_k=top_k, seed=seed)

    def _make_runtime() -> ServingRuntime:
        eng = PPREngine(g, slots=slots, threshold=threshold, backend=backend,
                        iters_per_step=iters_per_step)
        rt = ServingRuntime(eng, queue_depth=queue_depth)
        rt.serve(warm_qs)  # warm the trace outside the measured runs
        return rt

    runtime = _make_runtime()
    deadline_s = deadline_ms * 1e-3 if deadline_ms > 0 else None
    base = dict(n=g.n, m=g.m, backend=backend, slots=slots,
                threshold=threshold, iters_per_step=iters_per_step,
                queue_depth=queue_depth, mode="closed_loop",
                zipf_alpha=zipf_alpha,
                deadline_ms=deadline_ms if deadline_ms > 0 else None)
    records: list[dict] = []
    saturation = None
    for qps in qps_list:
        if runtime.engine.g is not g:
            # the previous rate's mid-stream updates mutated the engine's
            # graph; a fresh engine restores the pristine one
            runtime.close()
            runtime = _make_runtime()
        runtime.reset()
        cfg = LoadConfig(queries=queries, qps=float(qps), top_k=top_k,
                         zipf_alpha=zipf_alpha, seed=seed)
        qs, arrivals = make_workload(g.n, cfg)
        kwargs = {}
        if updates > 0:
            from repro.core.dynamic import make_update_injector

            kwargs = dict(
                update_injector=make_update_injector(
                    np.random.default_rng(seed), updates),
                update_at=(queries // 2,))
        rep = run_closed_loop(runtime, qs, arrivals, deadline_s=deadline_s,
                              **kwargs)
        records.append({**base, **rep.to_dict(),
                        "m_final": runtime.engine.g.m})
        if (rep.achieved_qps >= 0.9 * rep.offered_qps
                and rep.rejection_rate <= 0.01):
            saturation = max(saturation or 0.0, rep.offered_qps)
    runtime.close()
    return records, saturation


def _print_load(rec: dict) -> None:
    p99 = f"{rec['p99_ms']:.1f}ms" if rec["p99_ms"] is not None else "n/a"
    print(f"load[{rec['backend']}] offered={rec['offered_qps']:.1f}q/s "
          f"achieved={rec['achieved_qps']:.1f}q/s p99={p99} "
          f"queue mean={rec['queue_depth_mean']:.1f} "
          f"max={rec['queue_depth_max']:.0f} "
          f"rejected={rec['rejection_rate']:.1%} "
          f"expired={rec['expired']} cache_hits={rec['cache_hits']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=9, help="RMAT log2(n)")
    ap.add_argument("--avg-degree", type=int, default=8)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--threshold", type=float, default=1e-6)
    ap.add_argument("--backends", default="jax",
                    help="comma-separated subset of jax,pallas")
    ap.add_argument("--iters-per-step", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--load", action="store_true",
                    help="run the closed-loop offered-qps sweep too")
    ap.add_argument("--qps", default="8,32,128",
                    help="comma-separated offered rates for --load")
    ap.add_argument("--queue-depth", type=int, default=32)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-query queue-wait deadline (0 = none)")
    ap.add_argument("--zipf-alpha", type=float, default=1.1)
    ap.add_argument("--updates", type=int, default=0,
                    help="inject N random edge updates mid-stream per rate")
    ap.add_argument("--json", default=None, help="write the artifact here")
    args = ap.parse_args(argv)

    backends = [b for b in args.backends.split(",") if b]
    qps_list = [float(q) for q in args.qps.split(",") if q]
    oneshot: list[dict] = []
    closed_loop: list[dict] = []
    saturation: dict[str, float | None] = {}
    for backend in backends:
        rec = bench(scale=args.scale, avg_degree=args.avg_degree,
                    queries=args.queries, slots=args.slots,
                    threshold=args.threshold, backend=backend,
                    iters_per_step=args.iters_per_step, top_k=args.top_k,
                    seed=args.seed)
        oneshot.append(rec)
        print(f"ppr[{rec['backend']}] n={rec['n']} m={rec['m']} "
              f"slots={rec['slots']} queries={rec['queries']}: "
              f"{rec['qps']:.1f} q/s  p50={rec['p50_ms']:.1f}ms "
              f"p99={rec['p99_ms']:.1f}ms  mean_iters={rec['mean_iters']:.0f} "
              f"warm_hits={rec['warm_hits']}")
        if args.load:
            recs, sat = bench_load(
                scale=args.scale, avg_degree=args.avg_degree,
                queries=args.queries, slots=args.slots,
                threshold=args.threshold, backend=backend,
                iters_per_step=args.iters_per_step, top_k=args.top_k,
                seed=args.seed, qps_list=qps_list,
                queue_depth=args.queue_depth, deadline_ms=args.deadline_ms,
                zipf_alpha=args.zipf_alpha, updates=args.updates)
            closed_loop += recs
            saturation[backend] = sat
            for r in recs:
                _print_load(r)
            print(f"saturation[{backend}]: "
                  f"{sat if sat is not None else 'below lowest offered rate'}")

    if args.json:
        report = {"oneshot": oneshot, "closed_loop": closed_loop,
                  "saturation_qps": saturation}
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
