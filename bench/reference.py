"""Plain references the benchmark judges the program against.

Power iteration of ``x = (1 - d)·t + d·Aᵀ(x / outdeg)`` straight from the
edge list, with no layout, kernel, cache or batching of the program's:
``t`` is uniform for global PageRank and uniform over a seed set for PPR.
Dangling vertices (no out-edges) pass no mass on, as in the configurations
(``handle_dangling`` false).

The float64 versions run on the host and decide ``correct``.  The ``jnp``
versions take a dtype: in ``bfloat16``, the precision below the float32
that the configurations state, they are the control that the comparison
has to refuse.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

# The float64 references stop when a sweep moves each column by at most
# this in L1: a sweep contracts the error by d, so the L1 error left is at
# most THRESHOLD·d/(1 − d) < 6e-12, far under every limit.
THRESHOLD = 1e-12
MAX_ITER = 10_000
PPR_BATCH = 64


def walk_matrix(n: int, src: np.ndarray, dst: np.ndarray) -> sp.csr_matrix:
    """``A[v, u] = (number of edges u→v) / outdeg(u)``, float64."""
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    w = 1.0 / outdeg[src]
    return sp.csr_matrix((w, (dst, src)), shape=(n, n))


def teleport(n: int, seed_sets) -> np.ndarray:
    """``(n, k)`` float64 teleport columns: uniform over each seed set, and
    uniform over all vertices for an empty set."""
    t = np.zeros((n, len(seed_sets)))
    for j, seeds in enumerate(seed_sets):
        seeds = sorted(set(int(s) for s in seeds))
        if seeds:
            t[seeds, j] = 1.0 / len(seeds)
        else:
            t[:, j] = 1.0 / n
    return t


def power_iteration(a: sp.csr_matrix, t: np.ndarray, d: float) -> np.ndarray:
    """Jacobi iteration from ``t`` until a sweep moves no column by more
    than ``THRESHOLD`` in L1; raises after ``MAX_ITER`` sweeps."""
    x = t.copy()
    for _ in range(MAX_ITER):
        new = (1.0 - d) * t + d * (a @ x)
        err = np.abs(new - x).sum(axis=0).max()
        x = new
        if err <= THRESHOLD:
            return x
    raise RuntimeError(f"reference did not converge in {MAX_ITER} sweeps")


def pagerank(n: int, src, dst, d: float) -> np.ndarray:
    """Global PageRank, float64, ``(n,)``."""
    return power_iteration(walk_matrix(n, src, dst), teleport(n, [()]), d)[:, 0]


def ppr(n: int, src, dst, d: float, seed_sets) -> np.ndarray:
    """Personalized PageRank of each seed set, float64, ``(k, n)``; solved
    ``PPR_BATCH`` sets at a time so the host memory stays small."""
    a = walk_matrix(n, src, dst)
    out = np.empty((len(seed_sets), n))
    for lo in range(0, len(seed_sets), PPR_BATCH):
        sets = seed_sets[lo:lo + PPR_BATCH]
        out[lo:lo + len(sets)] = power_iteration(a, teleport(n, sets), d).T
    return out


def iterate_jnp(n: int, src, dst, d: float, t, dtype, sweeps: int):
    """``sweeps`` Jacobi sweeps of the same equation on the device, every
    value held in ``dtype``; ``t`` is ``(k, n)``.  Returns ``(k, n)``."""
    import jax
    import jax.numpy as jnp

    outdeg = np.bincount(src, minlength=n)
    inv = jnp.asarray(np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1), 0.0),
                      dtype)
    src_d, dst_d = jnp.asarray(src), jnp.asarray(dst)
    t = jnp.asarray(t, dtype)

    @jax.jit
    def run(t):
        def sweep(_, x):
            contrib = (x * inv)[:, src_d]
            acc = jax.vmap(lambda c: jax.ops.segment_sum(
                c, dst_d, num_segments=n))(contrib)
            return ((1.0 - d) * t + d * acc).astype(dtype)
        return jax.lax.fori_loop(0, sweeps, sweep, t)

    return run(t)
