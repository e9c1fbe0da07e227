"""The % of the chips' busy time in the traced window spent in collectives
(all-gather, all-reduce and their asynchronous halves: the exchange of the
rank vector and the residual's reduction), summed over the chips
(``bench/shard_work.py``).  Reads every ``exchange_share.<cell kind>``
metric."""
from bench import shard_work


def read(run, trace):
    s = shard_work.shares(run)
    return None if s is None else s["exchange"]
