"""The most real edges any shard holds over the mean, from the program's
``LAYOUTS["distributed"]["edges_per_shard"]``: the slowest shard sets every
round's pace.  Reads every ``shard_imbalance.<cell kind>`` metric."""
from bench import shard_work


def read(run, trace):
    lay = shard_work.layout()
    if not lay or not lay["edges_per_shard"]:
        return None
    edges = lay["edges_per_shard"]
    mean = sum(edges) / len(edges)
    return {"value": max(edges) / mean if mean else None,
            "edges_per_shard": edges}
