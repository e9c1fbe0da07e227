"""The benchmark's own graph generator: the edges of a configuration, made
from ``--seed``.

The Graph500 Kronecker generator (Graph500 specification, section 3.4, and
its reference ``kronecker_generator.m``): ``2**scale`` vertices and
``edgefactor · 2**scale`` edge tuples, each placed by ``scale`` draws of a
quadrant of the initiator ``[[A, B], [C, D]]``, then the vertex labels
permuted at random.  Self-loops and repeated tuples stay, as the generator
emits them; tuple ``(i, j)`` is the directed edge ``i → j``.  The
reference's final shuffle of the tuples' order is left out: the program and
the reference both sort the edges, so the order changes nothing.  Kept here
so that the reference and the program are fed from one edge list that
neither of them made.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int) -> np.random.Generator:
    """The generator every draw of a run starts from (any whole number)."""
    return np.random.default_rng(int(seed) % 2**64)


def edges(graph: dict, seed: int) -> tuple[int, np.ndarray, np.ndarray]:
    """``(n, src, dst)`` of the Kronecker graph of the configuration's
    ``scale``, ``edgefactor`` and ``initiator`` ``[A, B, C]``."""
    scale, n = int(graph["scale"]), 1 << int(graph["scale"])
    m = int(graph["edgefactor"]) * n
    a, b, c = graph["initiator"]
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    r = rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for level in range(scale):
        down = r.random(m) > ab
        right = r.random(m) > np.where(down, c_norm, a_norm)
        src |= down.astype(np.int64) << level
        dst |= right.astype(np.int64) << level
    perm = r.permutation(n)
    return n, perm[src].astype(np.int32), perm[dst].astype(np.int32)
