"""90th percentile, over the answered queries, of the time from a query's
due time to its answer less the time it spent in an engine slot
(``PPRResponse.latency_s``): generator lateness, queueing and harvest."""
from bench.serving import nearest_rank


def read(run, trace):
    return 1e3 * nearest_rank(run.facts["queue_waits_s"], 0.9)
