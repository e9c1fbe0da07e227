"""The control of a cell's comparison: the plain reference put in the
program's place, computed in bfloat16, the precision below the float32
that the configurations state.  The comparison that decides ``correct``
has to refuse it.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed it builds the cell's graph and, for a PPR cell, the seed
sets that a run with that seed asks for, runs the reference in bfloat16
on the device, and prints the numbers the cell compares, beside their
limits, as one JSON line.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
# sweeps of the control: 0.85**200 < 1e-14, so what is left is rounding
SWEEPS = 200


def global_checks(config: dict, seed: int, dtype) -> list:
    """The global cells' numbers for the reference solved in ``dtype``."""
    from bench import graph, reference

    n, src, dst = graph.edges(config["graph"], seed)
    d = config["damping"]
    x = reference.iterate_jnp(n, src, dst, d, np.full((1, n), 1.0 / n),
                              dtype, SWEEPS)
    got = np.asarray(x, np.float64)[0]
    l1 = float(np.abs(got - reference.pagerank(n, src, dst, d)).sum())
    return [("l1_max", l1, config["checks"]["l1_max"])]


def ppr_checks(config: dict, mix: dict, seed: int, count: int, dtype
               ) -> list:
    """The PPR cells' numbers for the top-k answers of the reference solved
    in ``dtype``, over the seed sets a run with ``seed`` asks first."""
    from bench import graph, queries, reference, serving

    n, src, dst = graph.edges(config["graph"], seed)
    asked = dict(enumerate(
        queries.seed_sets(n, count, mix, graph.rng(seed))))
    keys = sorted({tuple(sorted(set(s))) for s in asked.values()})
    x = reference.iterate_jnp(n, src, dst, config["damping"],
                              reference.teleport(n, keys).T, dtype, SWEEPS)
    rows = np.asarray(x.astype("float32"), np.float64)
    k = min(mix["top_k"], n)
    answers = {}
    for q, seeds in asked.items():
        row = rows[keys.index(tuple(sorted(set(seeds))))]
        idx = np.lexsort((np.arange(n), -row))[:k]
        answers[q] = types.SimpleNamespace(indices=idx, values=row[idx])
    run = types.SimpleNamespace(config=config, mix=mix)
    served = serving.Served(n, src, dst, None, None)
    checks, _ = serving.check(run, served, asked, answers)
    return checks


def cell_checks(spec: dict, seed: int, dtype, run_seconds: float) -> list:
    mix = spec["mix"]
    if mix["driver"] == "global_solve":
        return global_checks(spec["config"], seed, dtype)
    # the queries an open-loop run of ``run_seconds`` asks
    return ppr_checks(spec["config"], mix, seed,
                      round(mix["qps"] * run_seconds), dtype)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench.harness import cell_spec

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    spec = cell_spec(args.workload)
    seconds = json.loads((ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    for seed in args.seeds:
        checks = cell_checks(spec, seed, jnp.bfloat16, seconds)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "dtype": "bfloat16",
            "correct": all(v <= lim for _, v, lim in checks),
            "checks": {n: {"value": v, "limit": lim}
                       for n, v, lim in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
    raise SystemExit(main())
