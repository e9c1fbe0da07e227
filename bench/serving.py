"""What the PPR drivers share: the served deployment, its warm-up, and the
check of its answers against the float64 reference."""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench import graph, reference

# how long after the window the drivers wait for answers still in flight
DRAIN_S = 60.0


@dataclasses.dataclass
class Served:
    n: int
    src: np.ndarray
    dst: np.ndarray
    engine: object
    runtime: object

    def release(self) -> "Served":
        """Drop the program's state, so the reference runs without it."""
        self.engine = self.runtime = None
        return self


def build(run) -> Served:
    """Generate the graph, build ``ServingRuntime`` over ``PPREngine`` as the
    configuration states, compile by serving one query in every slot, and
    reset the engine and the result cache."""
    from repro.graphs.csr import Graph
    from repro.serving.ppr_engine import PPREngine, PPRQuery
    from repro.serving.runtime import ServingRuntime

    cfg, s = run.config, run.config["serving"]
    n, src, dst = graph.edges(cfg["graph"], run.seed)
    g = Graph.from_edges(n, src, dst)
    with run.timed("layout_build_s"):
        engine = PPREngine(
            g, slots=s["slots"], d=cfg["damping"], threshold=s["threshold"],
            handle_dangling=cfg["handle_dangling"], backend=s["backend"],
            iters_per_step=s["iters_per_step"])
    runtime = ServingRuntime(engine, queue_depth=run.mix["queue_depth"],
                             result_cache_size=s["result_cache_size"])
    # one query per slot: every slot's row update and read-back compiles
    # here, not in the window
    runtime.serve([PPRQuery(qid=-1 - i, seeds=(i,), top_k=run.mix["top_k"])
                   for i in range(s["slots"])])
    runtime.reset()
    return Served(n, src, dst, engine, runtime)


def check(run, served: Served, asked: dict, answers: dict) -> tuple[list, int]:
    """Compare every answer with the float64 reference of its query.

    ``asked`` maps qid -> seed set, ``answers`` qid -> ``PPRResponse``.
    Returns ``(checks, wrong)``: the numbers compared with their limits,
    and how many answers broke a limit.  For each answer:

    * ``value_err``: the largest gap between a served score and the
      reference's score of that vertex;
    * ``rank_gap``: how far the lowest-ranked served vertex's reference
      score lies below the reference's k-th best score (0 when the served
      set is the reference's top k);
    * ``malformed``: an answer of the wrong length or with repeated
      vertices.
    """
    cfg = run.config
    limits = cfg["checks"]
    k = min(run.mix["top_k"], served.n)
    keys = sorted({tuple(sorted(set(asked[q]))) for q in answers})
    ref = reference.ppr(served.n, served.src, served.dst, cfg["damping"], keys)
    row_of = {key: i for i, key in enumerate(keys)}
    worst_val = worst_gap = 0.0
    malformed = wrong = 0
    for qid, r in answers.items():
        row = ref[row_of[tuple(sorted(set(asked[qid])))]]
        idx = np.asarray(r.indices)
        if idx.size != k or np.unique(idx).size != k:
            malformed += 1
            wrong += 1
            continue
        kth = np.partition(row, row.size - k)[row.size - k]
        val = float(np.abs(np.asarray(r.values, np.float64) - row[idx]).max())
        gap = max(0.0, float(kth - row[idx].min()))
        worst_val, worst_gap = max(worst_val, val), max(worst_gap, gap)
        wrong += val > limits["value_err"] or gap > limits["rank_gap"]
    return [("value_err", worst_val, limits["value_err"]),
            ("rank_gap", worst_gap, limits["rank_gap"]),
            ("malformed", malformed, 0)], wrong


def device_steps(served: Served) -> int:
    """Jitted engine steps run since the last reset."""
    return served.engine.total_slot_steps // served.engine.slots


def nearest_rank(values, q: float) -> float:
    """The ``q``-quantile as one of the samples (nearest rank)."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    return float(v[max(0, math.ceil(q * v.size) - 1)])
