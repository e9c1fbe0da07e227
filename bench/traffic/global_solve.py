"""Global PageRank solves, back to back, each from the uniform start.

The mix names the registry variant (``variant``).  Set-up builds its
layout from the configuration and compiles one solve with the layout's
arrays as arguments; the window then calls that compiled solve until
``seconds`` have passed, each call ending in ``block_until_ready``.  Every
solve of the window is checked against the float64 reference: certified
(stopped at the threshold, not at ``max_iter``) and within the
configuration's L1 limit.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench import graph, reference
from bench.harness import Outcome


def _arrays(bundle) -> dict:
    items = bundle._asdict() if hasattr(bundle, "_asdict") else vars(bundle)
    return {k: x for k, x in items.items() if isinstance(x, jax.Array)}


def _with_arrays(bundle, arrays: dict):
    if hasattr(bundle, "_replace"):
        return bundle._replace(**arrays)
    return dataclasses.replace(bundle, **arrays)


def run(run) -> Outcome:
    from repro.core.solver import get_variant
    from repro.graphs.csr import Graph

    cfg = run.config
    n, src, dst = graph.edges(cfg["graph"], run.seed)
    g = Graph.from_edges(n, src, dst)
    variant = get_variant(run.mix["variant"])
    with run.timed("layout_build_s"):
        bundle = variant.build(g, **cfg["layout"])
        arrays = jax.block_until_ready(_arrays(bundle))
    opts = dict(d=cfg["damping"], threshold=cfg["threshold"],
                max_iter=cfg["max_iter"],
                handle_dangling=cfg["handle_dangling"])
    solve = jax.jit(
        lambda a: variant.run(_with_arrays(bundle, a), **opts)
    ).lower(arrays).compile()
    jax.block_until_ready(solve(arrays))  # warm-up

    results = []
    t0 = run.open_window()
    while True:
        with run.span("solve"):
            results.append(jax.block_until_ready(solve(arrays)))
        elapsed = time.perf_counter() - t0
        if elapsed >= run.seconds:
            break
    run.close_window()
    run.note_memory()

    sweeps = [int(r.iterations) for r in results]
    certified = [float(r.err) <= cfg["threshold"] for r in results]
    ranks = [np.asarray(r.pr, np.float64) for r in results]
    run.facts.update(n=n, m=src.size, rows=1, solves=len(results),
                     sweeps=sum(sweeps))
    del results, solve, arrays, bundle
    exact = reference.pagerank(n, src, dst, cfg["damping"])
    l1 = [float(np.abs(r - exact).sum()) for r in ranks]
    limit = cfg["checks"]["l1_max"]
    failed = sum(not c or e > limit for c, e in zip(certified, l1))
    checks = [("l1_max", max(l1), limit),
              ("uncertified", certified.count(False), 0)]
    return Outcome({"solve_s": elapsed / len(ranks)}, checks,
                   attempted=len(ranks), failed=failed)
