"""Oracles for the blocked SpMV kernels: pure-jnp segment sums for the
Jacobi kernel, and a float64 numpy sweep for the Gauss–Seidel passes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.csr import BlockedCOO, Graph


def spmv_ref(contrib: jax.Array, src: jax.Array, dst: jax.Array, n: int) -> jax.Array:
    """acc[u] = sum over edges (v,u) of contrib[v] — the SpMV inside Eq (1)."""
    return jax.ops.segment_sum(contrib[src], dst, num_segments=n)


def spmv_blocked_ref(contrib_blocks: jax.Array, b: BlockedCOO) -> jax.Array:
    """Same tile semantics as the kernel, expressed with plain segment sums —
    used to check the blocked layout itself is a faithful edge permutation
    (weight-scaled per edge when the layout carries ``tiles_weight``)."""
    n_blocks, block = contrib_blocks.shape
    flat = contrib_blocks.reshape(-1)
    src_glob = jnp.asarray(b.tile_src_block)[:, None] * block + jnp.asarray(b.tiles_src_local)
    dst_glob = jnp.asarray(b.tile_dst_block)[:, None] * block + jnp.asarray(b.tiles_dst_local)
    lane_w = b.tiles_valid if b.tiles_weight is None else b.tiles_weight
    vals = flat[src_glob.reshape(-1)] * jnp.asarray(lane_w).reshape(-1)
    acc = jax.ops.segment_sum(vals, dst_glob.reshape(-1), num_segments=n_blocks * block)
    return acc.reshape(n_blocks, block)


def spmv_gs_pass_ref(g: Graph, pr, *, block: int, d: float, base,
                     bias=None, dmass=0.0, frozen=None) -> np.ndarray:
    """One Gauss–Seidel sweep as :func:`~repro.kernels.spmv.spmv_gs_pass`
    and :func:`~repro.kernels.spmv.spmv_gs_pass_multi` define it, in
    float64 from the graph's edges (no tile layout).

    ``pr`` is ``(n,)`` or ``(rows, n)``: the ranks at the sweep's start.
    Dst blocks of ``block`` vertices commit in increasing order.  Block
    ``k`` sums ``pr[v] · weight / outdeg(v)`` over its in-edges, reading
    ``pr[v]`` as already committed this sweep when ``v``'s block is below
    ``k`` and from the sweep's input otherwise (a block commits only after
    its last tile, so its own sources read the input), then commits
    ``base · bias + dmass + d · acc``.  ``base``, ``bias`` (``None``: ones),
    ``dmass`` and ``frozen`` broadcast against ``pr``: the single-vector
    kernel's scalars and per-vertex bias and freeze mask, or the multi
    kernel's per-row base panel and ``(rows, 1)`` row freeze.  Frozen
    entries keep their input value."""
    pr0 = np.asarray(pr, np.float64)
    cur = pr0.copy()
    bias = 1.0 if bias is None else np.asarray(bias, np.float64)
    teleport = np.broadcast_to(np.asarray(base, np.float64) * bias
                               + np.asarray(dmass, np.float64), pr0.shape)
    held = np.broadcast_to(np.zeros(pr0.shape, bool) if frozen is None
                           else np.asarray(frozen, bool), pr0.shape)
    src = np.asarray(g.src)
    inv_out = np.zeros(g.n)
    np.divide(1.0, g.out_degree, out=inv_out, where=g.out_degree > 0)
    w = inv_out[src] if g.weights is None else inv_out[src] * g.weights
    rows = int(np.prod(pr0.shape[:-1]))
    for lo in range(0, g.n, block):
        hi = min(lo + block, g.n)
        e0, e1 = int(g.in_ptr[lo]), int(g.in_ptr[hi])
        s = src[e0:e1]
        ranks = np.where(s < lo, cur[..., s], pr0[..., s]) * w[e0:e1]
        local = np.asarray(g.dst[e0:e1]) - lo
        acc = np.stack([np.bincount(local, r, hi - lo)
                        for r in ranks.reshape(rows, -1)])
        cur[..., lo:hi] = np.where(
            held[..., lo:hi], pr0[..., lo:hi],
            teleport[..., lo:hi] + d * acc.reshape(pr0.shape[:-1] + (-1,)))
    return cur
