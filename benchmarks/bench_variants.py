"""Fig 1/2 — speedup per parallel variant on standard + synthetic datasets.

Variants are enumerated from the registry (``repro.core.solver``) and driven
purely by registry **metadata**, so a newly registered variant shows up in
this table for free — correctly:

  * ``Variant.layout`` keys bundle sharing (one build per layout per dataset;
    the pallas tile bucketing and DeviceGraph conversion are the expensive
    host-side steps);
  * ``Variant.backend`` flags interpret-mode Pallas runs (``interpreted=1``)
    and skips the host oracle;
  * ``Variant.schedule`` picks the simulator discipline.

Two measurements per (dataset × variant):

  * real single-device wall time of the jitted solver (CPU; absolute);
  * simulated 56-worker makespan under the event-driven cost model
    (repro.core.runtime) with lognormal per-sweep jitter scaled by the actual
    per-partition edge loads of the equal-vertex allocation — this is what
    reproduces the paper's *relative* claims (no-sync > barrier) on a box
    with one core. Speedup = simulated sequential time / simulated variant
    makespan.

``--json PATH`` additionally writes the records as JSON (the ``check.sh``
perf-trajectory artifact ``BENCH_variants.json``).  Records of variants with
a blocked (tiled) bundle carry its ``tile_occupancy`` counters, and
``--reorder {none,bfs,degree,random}`` benches under a vertex reordering
(``repro.graphs.reorder``) — together they measure how much locality
ordering raises tile occupancy, the payoff the build pipeline's reorder
stage is for.

``--assert-trajectories`` turns the artifact into a **regression gate**: the
current per-variant iteration/sweep counts are compared against the pinned
envelopes in ``tests/data/trajectory_envelopes.json`` and any >10% iteration
regression (or any sweep regression past the same margin) fails the run.
``--pin-trajectories`` (re)writes the envelope file from the current run —
do that deliberately, with the bench config the envelopes were pinned under
(check.sh's), and commit the diff.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib

import numpy as np

from benchmarks.common import BENCH_DATASETS, SCALE_DOWN, csv_row, time_call
from repro.core import PartitionedGraph, l1_norm, pagerank_numpy
from repro.core.solver import get_variant, list_variants, plan_stats
from repro.core.runtime import simulate_jittered
from repro.graphs import make_dataset
from repro.utils.platform import pallas_interpret

THRESH = 1e-8
P = 56  # the paper's thread count
# fixed exchange staleness for the distributed nosync variants, passed
# explicitly so the cost model knows sweeps-per-round (= this) exactly
LOCAL_SWEEPS = 2
# delayed/stale-sweep replay regime: 10% of executed sweeps stall for 5
# mean-sweep units (simulate_jittered docstring) — the regime where the
# adaptive schedule's shed sweeps also shed their stall exposure
STALL_PROB, STALL_DUR = 0.1, 5.0

ENVELOPE_PATH = (pathlib.Path(__file__).resolve().parents[1]
                 / "tests" / "data" / "trajectory_envelopes.json")


def bench_records(name: str, scale_down: float = SCALE_DOWN,
                  reorder: str = "none") -> list[dict]:
    g = make_dataset(name, scale_down=scale_down)
    if reorder != "none":
        from repro.graphs.reorder import compute_order, permute_graph

        g = permute_graph(g, compute_order(g, reorder))
    ref, it_seq = pagerank_numpy(g, threshold=1e-12)
    pg = PartitionedGraph.from_graph(g, p=P)
    # actual per-partition edge loads of the equal-vertex allocation drive
    # the cost model (the skew edge-balanced boundaries would remove)
    rel_costs = np.asarray(pg.emask, dtype=np.float64).sum(axis=1)
    records = []

    # one build per bundle layout (registry metadata), shared across variants
    bundles = {"partitioned": pg}  # the simulator's pg doubles as the nosync bundle

    sim_seq = None
    for vname in list_variants():
        v = get_variant(vname)
        if v.backend == "numpy":
            continue  # the oracle is the reference, not a competitor
        kind = v.layout or vname
        if kind not in bundles:
            bundles[kind] = v.build(g, threads=P)
        bundle = bundles[kind]
        fn = lambda: v.run(bundle, threshold=THRESH, local_sweeps=LOCAL_SWEEPS)
        r = fn()
        wall = time_call(fn)
        iters = int(r.iterations)
        exec_sweeps = None if r.sweeps is None else int(r.sweeps)
        # simulated 56-worker makespan with jitter, discipline from metadata.
        # Distributed nosync variants report exchange ROUNDS with
        # LOCAL_SWEEPS sweeps each — the cost model counts sweeps, so scale.
        discipline = (v.schedule
                      if v.schedule in ("barrier", "nosync", "adaptive")
                      else "barrier")
        sweeps = iters * (LOCAL_SWEEPS
                          if v.backend == "shard_map" and v.schedule == "nosync"
                          else 1)
        # adaptive variants replay their measured sweep activity: the cost
        # model Bernoulli-samples the executed/possible rate, so shed sweeps
        # shed their simulated cost (and their stall exposure below)
        active = None
        if discipline == "adaptive" and exec_sweeps and iters:
            units = int(getattr(bundle, "p", 0) or
                        getattr(bundle, "n_blocks", 0) or 1)
            active = min(1.0, exec_sweeps / (iters * units))
        ps = plan_stats(bundle)
        if ps:
            # plan-staged variants sweep only the shrunken CORE — charge the
            # cost model with the core's partition loads and scale the
            # makespan by the edge-work ratio (rel_costs is normalized to
            # mean 1 inside the simulator, so absolute size must be applied
            # here), or the artifact would hide the very payoff the
            # decomposition exists to buy
            pg_core = PartitionedGraph.from_graph(bundle.plan.core, p=P)
            core_rel = np.asarray(pg_core.emask, dtype=np.float64).sum(axis=1)
            scale = max(ps["core_m"], 1) / max(g.m, 1)
            sim = simulate_jittered(
                pg_core, discipline, iterations=sweeps, seed=1,
                rel_costs=core_rel, active=active,
            ) * scale
            sim_stalled = simulate_jittered(
                pg_core, discipline, iterations=sweeps, seed=1,
                rel_costs=core_rel, active=active,
                stall_prob=STALL_PROB, stall_dur=STALL_DUR,
            ) * scale
        else:
            sim = simulate_jittered(pg, discipline, iterations=sweeps, seed=1,
                                    rel_costs=rel_costs, active=active)
            sim_stalled = simulate_jittered(
                pg, discipline, iterations=sweeps, seed=1,
                rel_costs=rel_costs, active=active,
                stall_prob=STALL_PROB, stall_dur=STALL_DUR)
        if sim_seq is None:
            # "barrier" sorts first, so its iteration count is already in hand
            it_b = iters if vname == "barrier" else int(
                get_variant("barrier").run(
                    get_variant("barrier").build(g), threshold=THRESH
                ).iterations
            )
            sim_seq = simulate_jittered(pg, "sequential", iterations=it_b,
                                        seed=1, rel_costs=rel_costs)
            sim_seq_stalled = simulate_jittered(
                pg, "sequential", iterations=it_b, seed=1,
                rel_costs=rel_costs, stall_prob=STALL_PROB,
                stall_dur=STALL_DUR)
        # record the core-graph size (and the chain-contraction edge
        # counters) so the JSON shows the preprocessing payoff, not just
        # wall time
        records.append({
            "dataset": name,
            "variant": vname,
            "reorder": reorder,
            # occupancy counters of the variant's tiled bundle (None for
            # untiled layouts) — the fraction of kernel lanes doing real
            # edge work, the number vertex reordering exists to raise
            "tile_occupancy": _tile_occupancy(bundle),
            "wall_us": wall * 1e6,
            "iters": iters,
            # executed schedule-unit updates (PageRankResult.sweeps) — the
            # work metric the adaptive schedules shrink; None for solvers
            # that own their loop
            "sweeps": exec_sweeps,
            "sim_speedup_vs_seq": sim_seq / sim,
            # same makespan model under the delayed/stale-sweep regime
            # (STALL_PROB/STALL_DUR): barrier pays every stall at the round
            # max, nosync localizes it, adaptive also sheds the stalls of
            # the sweeps it skipped
            "sim_stalled_speedup_vs_seq": sim_seq_stalled / sim_stalled,
            "l1_vs_oracle": l1_norm(r.pr, ref),
            "interpreted": bool(v.backend == "pallas" and pallas_interpret()),
            "core_n": ps["core_n"] if ps else g.n,
            "core_m": ps["core_m"] if ps else g.m,
            "pruned_edges": ps["pruned_edges"] if ps else 0,
            "contracted_edges": ps["contracted_edges"] if ps else 0,
            # per-round observed-error trajectory from the engine (empty for
            # solvers that own their loop, e.g. the shard_map modes) — the
            # artifact shows convergence curves, not just endpoints
            "residuals": _trajectory(r, iters),
            # static-analyzer VMEM estimate for the kernel this variant runs
            # (None for non-Pallas backends) — the artifact carries the
            # budget its kernel was certified under, so an over-budget
            # config is visible next to the wall time it produced
            "vmem": _variant_vmem(v),
        })
    return records


def _tile_occupancy(bundle) -> dict | None:
    """Occupancy counters of a bundle's blocked tile layout, when it has one
    (plan-staged bundles are unwrapped to their inner core bundle)."""
    from repro.graphs.csr import tile_occupancy_stats

    inner = getattr(bundle, "bundle", bundle)
    tv = getattr(inner, "tiles_valid", None)
    if tv is None:
        return None
    valid = np.asarray(tv)
    return tile_occupancy_stats(n_edges=int(valid.sum()),
                                n_tiles=int(valid.shape[0]),
                                tile_cap=int(valid.shape[1]))


def _variant_vmem(v) -> dict | None:
    from repro.analysis.vmem import variant_vmem

    return variant_vmem(v)


def _trajectory(r, iters: int) -> list[float]:
    """Engine residual trajectory as a JSON-friendly list (see
    ``PageRankResult.residuals``: inf-padded ``(max_iter,)`` buffer)."""
    if r.residuals is None:
        return []
    errs = np.asarray(r.residuals, dtype=np.float64)[:iters]
    return [float(f"{e:.4e}") for e in errs[np.isfinite(errs)]]


def _rows(records: list[dict]) -> list[str]:
    rows = []
    for rec in records:
        derived = (f"iters={rec['iters']};"
                   f"sim_speedup_vs_seq={rec['sim_speedup_vs_seq']:.1f};"
                   f"l1={rec['l1_vs_oracle']:.2e}")
        if rec["interpreted"]:
            derived += ";interpreted=1"
        rows.append(csv_row(f"fig1_2/{rec['dataset']}/{rec['variant']}",
                            rec["wall_us"], derived))
    return rows


def pin_trajectories(records: list[dict], scale_down: float, reorder: str,
                     path: pathlib.Path = ENVELOPE_PATH) -> None:
    """(Re)write the pinned convergence envelopes from the current run."""
    env = {
        "_meta": {"thresh": THRESH, "p": P, "scale_down": float(scale_down),
                  "reorder": reorder},
        "records": {
            f"{r['dataset']}/{r['variant']}": {
                "iters": r["iters"],
                "sweeps": r["sweeps"],
                "residuals": r["residuals"],
            }
            for r in records
        },
    }
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(env, f, indent=1)
        f.write("\n")


def assert_trajectories(records: list[dict], scale_down: float, reorder: str,
                        path: pathlib.Path = ENVELOPE_PATH,
                        margin: float = 0.10) -> int:
    """Fail (SystemExit) when any record regresses >``margin`` past its
    pinned envelope — iteration counts and executed sweep counts both gate.
    Returns the number of records actually compared; variants not yet
    pinned pass (pin them deliberately with ``--pin-trajectories``)."""
    if not path.exists():
        raise SystemExit(
            f"--assert-trajectories: no envelope file at {path}; "
            "run with --pin-trajectories first (and commit the file)")
    with open(path) as f:
        env = json.load(f)
    meta = env["_meta"]
    if (not math.isclose(float(meta["scale_down"]), float(scale_down))
            or meta["reorder"] != reorder or meta["thresh"] != THRESH):
        raise SystemExit(
            f"--assert-trajectories: envelope pinned under "
            f"scale_down={meta['scale_down']} reorder={meta['reorder']!r} "
            f"thresh={meta['thresh']}, but this run used "
            f"scale_down={scale_down} reorder={reorder!r} thresh={THRESH} — "
            "convergence counts are config-dependent; match the config or "
            "re-pin")
    failures, compared = [], 0
    for r in records:
        pinned = env["records"].get(f"{r['dataset']}/{r['variant']}")
        if pinned is None:
            continue
        compared += 1
        limit = math.ceil(pinned["iters"] * (1.0 + margin))
        if r["iters"] > limit:
            failures.append(
                f"{r['dataset']}/{r['variant']}: {r['iters']} iterations "
                f"vs pinned {pinned['iters']} (limit {limit})")
        if pinned.get("sweeps") and r.get("sweeps"):
            s_limit = math.ceil(pinned["sweeps"] * (1.0 + margin))
            if r["sweeps"] > s_limit:
                failures.append(
                    f"{r['dataset']}/{r['variant']}: {r['sweeps']} sweeps "
                    f"vs pinned {pinned['sweeps']} (limit {s_limit})")
    if failures:
        raise SystemExit(
            "trajectory regression (>10% past pinned envelope):\n  "
            + "\n  ".join(failures))
    return compared


def main(datasets=None, scale_down: float = SCALE_DOWN,
         json_path: str | None = None, reorder: str = "none",
         pin: bool = False, assert_envelopes: bool = False) -> list[str]:
    records = []
    for ds in (datasets or BENCH_DATASETS):
        records += bench_records(ds, scale_down=scale_down, reorder=reorder)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(records, f, indent=1)
    if pin:
        pin_trajectories(records, scale_down=scale_down, reorder=reorder)
    if assert_envelopes:
        n = assert_trajectories(records, scale_down=scale_down,
                                reorder=reorder)
        print(f"trajectory envelopes OK ({n} records within 10%)")
    return _rows(records)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", default=None,
                    help="comma-separated subset (default: all bench datasets)")
    ap.add_argument("--scale-down", type=float, default=SCALE_DOWN)
    ap.add_argument("--json", default=None, help="also write records as JSON")
    ap.add_argument("--reorder", choices=("none", "bfs", "degree", "random"),
                    default="none",
                    help="bench under a vertex reordering; blocked records'"
                         " tile_occupancy shows the locality payoff")
    ap.add_argument("--pin-trajectories", action="store_true",
                    help="(re)write tests/data/trajectory_envelopes.json "
                         "from this run")
    ap.add_argument("--assert-trajectories", action="store_true",
                    help="fail on >10%% iteration/sweep regressions vs the "
                         "pinned envelopes")
    args = ap.parse_args()
    ds = args.datasets.split(",") if args.datasets else None
    print("\n".join(main(ds, scale_down=args.scale_down, json_path=args.json,
                         reorder=args.reorder, pin=args.pin_trajectories,
                         assert_envelopes=args.assert_trajectories)))
