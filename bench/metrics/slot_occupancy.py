"""The % of the engine's batch slots that held a query, over the engine
steps of the traced window: Σ ``active`` ÷ Σ ``slots`` of the program's
``ppr.step`` spans."""
from bench import program_spans


def read(run, trace):
    return value(program_spans.of_run(run))


def value(t):
    steps = t.named("ppr.step")
    slots = sum(s.args["slots"] for s in steps)
    if not slots:
        return None
    return 100.0 * sum(s.args["active"] for s in steps) / slots
