"""90th percentile (nearest rank), over the queries admitted in the traced
window, of the time each waited in the serving runtime's queue for a slot:
``queue_ms`` of the program's ``ppr.admit`` span, on the runtime's clock
from the offer to the pop.  Unlike ``queue_wait_ms`` it leaves out how late
the generator offered and the harvest."""
from bench import program_spans
from bench.serving import nearest_rank


def read(run, trace):
    return value(program_spans.of_run(run))


def value(t):
    waits = [s.args["queue_ms"] for s in t.named("ppr.admit")]
    return nearest_rank(waits, 0.9) if waits else None
