"""Sweeps per certified solve: ``PageRankResult.iterations``, the mean over
the window's solves."""


def read(run, trace):
    return run.facts["sweeps"] / run.facts["solves"]
