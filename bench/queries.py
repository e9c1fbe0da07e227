"""PPR query traffic from a seed.

A copy of ``repro.serving.loadgen.make_workload``'s mix: seeds Zipf-ranked
(``zipf_alpha``) over a permutation of all vertices, so vertex id and
popularity are unrelated; exact repeats of earlier seed sets
(``repeat_fraction``); 2–4-seed sets (``multi_seed_fraction``); global
queries with no seed (``global_fraction``); the rest single seeds.

Two changes keep the work of a run the same from seed to seed: each kind
comes a fixed number of times, in an order drawn from the seed, and the
open loop's gaps between arrivals are a fixed set (the quantiles of the
exponential distribution, which Poisson arrivals have), also in an order
drawn from the seed.  The seed still draws which vertices are asked for.
"""
from __future__ import annotations

import numpy as np

KINDS = ("single", "repeat", "multi", "global")


def kinds(count: int, mix: dict, rng: np.random.Generator) -> list:
    """``count`` query kinds in the mix's proportions, shuffled; the first
    is never a repeat (there is nothing to repeat yet)."""
    n_rep = round(count * mix["repeat_fraction"])
    n_multi = round(count * mix["multi_seed_fraction"])
    n_glob = round(count * mix["global_fraction"])
    out = (["repeat"] * n_rep + ["multi"] * n_multi + ["global"] * n_glob)
    out += ["single"] * (count - len(out))
    order = [out[i] for i in rng.permutation(count)]
    if order[0] == "repeat":
        j = next(i for i, k in enumerate(order) if k != "repeat")
        order[0], order[j] = order[j], order[0]
    return order


class Stream:
    """The seed sets of an endless query stream, taken in chunks; each
    chunk holds the mix's kinds in fixed numbers, and a repeat may repeat
    any earlier query of the stream."""

    def __init__(self, n: int, mix: dict, rng: np.random.Generator):
        self.n, self.mix, self.rng = n, mix, rng
        self.ranked = rng.permutation(n)  # popularity rank -> vertex
        cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64)
                        ** -float(mix["zipf_alpha"]))
        self.cdf = cdf / cdf[-1]
        self.asked: list[tuple[int, ...]] = []

    def _draw(self, k: int) -> np.ndarray:
        ranks = np.searchsorted(self.cdf, self.rng.random(k), side="right")
        return self.ranked[np.minimum(ranks, self.n - 1)]

    def take(self, count: int) -> list[tuple[int, ...]]:
        """The next ``count`` seed sets."""
        rng, out = self.rng, self.asked
        start = len(out)
        for kind in kinds(count, self.mix, rng):
            if kind == "repeat" and out:
                seeds = out[int(rng.integers(0, len(out)))]
            elif kind == "global":
                seeds = ()
            elif kind == "multi":
                seeds = tuple(sorted({int(v) for v in
                                      self._draw(int(rng.integers(2, 5)))}))
            else:
                seeds = (int(self._draw(1)[0]),)
            out.append(seeds)
        return out[start:]


def seed_sets(n: int, count: int, mix: dict, rng: np.random.Generator
              ) -> list[tuple[int, ...]]:
    """The seed sets of ``count`` queries."""
    return Stream(n, mix, rng).take(count)


def arrivals(count: int, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due times in ``[0, seconds)`` of ``count`` open-loop queries: the
    first at 0, then gaps that are the exponential distribution's
    ``count`` quantiles in a shuffled order, scaled so that the ``count``
    gaps fill ``seconds`` (the offered rate is ``count / seconds``)."""
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q)[rng.permutation(count)]
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
