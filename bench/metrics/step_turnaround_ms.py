"""Median time the chip sat idle between one engine step's device program
and the next's, over the steps after which work was left (``active_after +
queued > 0`` on the step's ``ppr.step`` span), so the next step could have
started at once: the host's harvest, admission and dispatch.  ``by_span``
splits the mean idle gap by the innermost program span the host was in,
on the host's clock; the two clocks differ by about a millisecond, so that
split is approximate."""
import statistics
from collections import Counter

from bench import program_spans as ps


def read(run, trace):
    return value(ps.of_run(run))


def value(t):
    gaps, by_span = [], Counter()
    for chip in sorted(t.modules):
        steps = ps.steps(t, chip)
        for (st, _, mod), (_, _, nxt) in zip(steps, steps[1:]):
            if st.args["active_after"] + st.args["queued"] <= 0:
                continue
            idle = ps.idle_between(t.ops.get(chip, []), mod[2], nxt[1])
            gaps.append(sum(e - s for s, e in idle))
            for s, e in idle:
                by_span += ps.innermost(t.spans, s, e)
    if not gaps:
        return None
    return {"value": 1e-6 * statistics.median(gaps),
            "mean": 1e-6 * statistics.mean(gaps), "gaps": len(gaps),
            "by_span": {k: 1e-6 * v / len(gaps)
                        for k, v in by_span.most_common()}}
