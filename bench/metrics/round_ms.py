"""Busy device milliseconds per round of a sharded solve: each chip's busy
time in the traced window (the union of its operations), mean over the
chips, over the rounds the window's solves ran (``bench/shard_work.py``).
Reads every ``round_ms.<cell kind>`` metric."""
from bench import shard_work


def read(run, trace):
    s = shard_work.shares(run)
    return None if s is None else s["round_ms"]
