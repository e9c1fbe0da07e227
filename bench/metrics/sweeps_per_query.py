"""Mean sweeps a query held its engine slot for (``sweeps`` of the
program's ``ppr.harvest`` spans in the traced window), with
``past_convergence``, the mean sweeps run after the row first met the
threshold (``sweeps − converged_sweep``: an engine step ends only every
``iters_per_step`` sweeps), and ``warm_share``, the % of harvested queries
that started from the warm cache."""
from bench import program_spans


def read(run, trace):
    return value(program_spans.of_run(run))


def value(t):
    h = t.named("ppr.harvest")
    if not h:
        return None
    return {"value": sum(s.args["sweeps"] for s in h) / len(h),
            "past_convergence": sum(s.args["sweeps"]
                                    - s.args["converged_sweep"]
                                    for s in h) / len(h),
            "warm_share": 100.0 * sum(bool(s.args["warm"]) for s in h)
            / len(h)}
