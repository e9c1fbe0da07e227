"""Open-loop PPR traffic: independent users whose queries fall due at fixed
times, whether or not the server keeps up.

The mix fixes the offered rate (``qps``), the query mix and the admission
queue's depth; the run offers ``qps × seconds`` queries due over the
window.  Each query is timed from when it fell due to when its answer was
harvested, so a stall that delays later offers counts against them; how
late the generator offered is printed apart.  Queries still in flight when
the window closes are followed to their answer, for up to ``DRAIN_S``.  A
query rejected by the full queue, expired, or never answered is missed,
and counts as having waited until the drain gave up.
"""
from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

from bench import graph, queries, serving
from bench.harness import Outcome
from bench.serving import nearest_rank


@dataclasses.dataclass
class Window:
    """What one open-loop window offered and got back (times on the host's
    ``perf_counter``)."""

    asked: dict  # qid -> seed set
    due: np.ndarray  # qid -> due time
    offered_at: dict  # qid -> when the offer returned
    answers: dict  # qid -> PPRResponse
    harvested: dict  # qid -> when its answer came back
    rejected: set
    give_up: float  # when the drain stopped waiting
    pending_at_close: int = -1  # queued or in a slot when the window closed

    @property
    def lost(self) -> set:
        return set(self.asked) - set(self.answers) - self.rejected

    def latencies_s(self) -> list:
        """Due to harvest for every query; a missed one waited until the
        drain gave up."""
        return [self.harvested.get(q, self.give_up) - self.due[q]
                for q in self.asked]


def offer(run, runtime, qps: float, drain_s: float) -> Window:
    """Offer one window of the mix at ``qps`` and drain for up to
    ``drain_s``; the trace (if any) covers the window and the drain."""
    from repro.serving.ppr_engine import PPRQuery

    mix = run.mix
    rng = graph.rng(run.seed)
    count = round(qps * run.seconds)
    asked = dict(enumerate(
        queries.seed_sets(runtime.engine.g.n, count, mix, rng)))
    offsets = queries.arrivals(count, run.seconds, rng)

    t0 = run.open_window()
    due, close = t0 + offsets, t0 + run.seconds
    w = Window(asked, due, {}, {}, {}, set(), close + drain_s)
    i = 0
    while True:
        now = time.perf_counter()
        if w.pending_at_close < 0 and now >= close:
            w.pending_at_close = runtime.pending
        while i < count and due[i] <= now:
            with run.span("offer"):
                adm = runtime.offer(PPRQuery(qid=i, seeds=asked[i],
                                             top_k=mix["top_k"]))
            w.offered_at[i] = time.perf_counter()
            if adm.status == "rejected":
                w.rejected.add(i)
            elif adm.response is not None:
                w.harvested[i], w.answers[i] = w.offered_at[i], adm.response
            i += 1
        if runtime.pending and now < w.give_up:
            with run.span("pump"):
                out = runtime.pump()
            t = time.perf_counter()
            for r in out:
                w.harvested[r.qid], w.answers[r.qid] = t, r
        elif i < count:
            with run.span("wait"):
                time.sleep(max(0.0, due[i] - time.perf_counter()))
        else:
            break
    run.close_window()
    w.pending_at_close = max(w.pending_at_close, 0)
    late = np.array([w.offered_at[q] - due[q] for q in w.offered_at])
    print(f"generator lateness over {late.size} offers: max "
          f"{1e3 * late.max():.3f} ms, p90 {1e3 * nearest_rank(late, 0.9):.3f}"
          f" ms", file=sys.stderr)
    return w


def run(run) -> Outcome:
    served = serving.build(run)
    w = offer(run, served.runtime, run.mix["qps"], serving.DRAIN_S)
    run.note_memory()
    run.facts.update(
        n=served.n, m=served.src.size, rows=served.engine.slots,
        sweeps=serving.device_steps(served) * served.engine.iters_per_step,
        queue_waits_s=[w.harvested[q] - w.due[q] - r.latency_s
                       for q, r in w.answers.items()])
    checks, wrong = serving.check(run, served.release(), w.asked, w.answers)
    checks.append(("lost", len(w.lost), 0))
    return Outcome({"ppr_p90_ms": 1e3 * nearest_rank(w.latencies_s(), 0.9)},
                   checks, attempted=len(w.asked),
                   failed=len(w.rejected) + len(w.lost) + wrong)
