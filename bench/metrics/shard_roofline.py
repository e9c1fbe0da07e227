"""Each chip's share of its roofline in a round of a sharded solve: the
bytes its shard's round has to move (``bench/shard_work.py``: 8 B per real
edge of the shard, 4 B per vertex for each of the gathered ranks, the
inverse out-degrees and the dangling mask, 4 B per owned vertex written)
over the chip's HBM bandwidth, divided by its busy time per round less its
collectives, in %.  ``value`` is the mean over the chips, ``per_chip`` each
by device id.  Reads every ``shard_roofline.<cell kind>`` metric."""
from bench import shard_work


def read(run, trace):
    s = shard_work.shares(run)
    if s is None or not s["roofline"]:
        return None
    roof = s["roofline"]
    return {"value": sum(roof.values()) / len(roof), "bound": "memory",
            "per_chip": {str(k): v for k, v in roof.items()}}
