"""The plain reference of the sharded cells: PageRank as LDBC Graphalytics
specifies it, in float64 on the host, straight from the edge list.

``iterations`` synchronous (Jacobi) iterations from ``1/n`` of

    x'(v) = (1 − d)/n + d · Σ_{u→v} x(u)/outdeg(u) + d · Σ_{outdeg(w)=0} x(w)/n

(dangling mass spread uniformly every iteration).  None of the program's
layout, partitioning or float32 arithmetic is used: the edges are grouped
here into blocks of 65,536 destinations, each block a CSR matrix whose row
pointers are a count and whose columns are its edges' sources in
destination order (a stable 16-bit radix argsort, no comparison sort), and
each iteration multiplies the blocks on a thread pool (scipy and numpy
release the GIL there).  Host memory beyond the edge list: one sorted copy
of it while the blocks are built, then 5 bytes per edge (int32 columns,
int8 ones) and 25 per vertex.
"""
from __future__ import annotations

import concurrent.futures
import os

import numpy as np
import scipy.sparse as sp

ROWS = 1 << 16  # destinations per block
CHUNK = 1 << 22  # edges per grouping task


def _workers(workers):
    return workers or min(32, os.cpu_count() or 1)


def out_degree(n: int, src: np.ndarray, pool, parts: int) -> np.ndarray:
    """``(n,)`` int64 out-degrees, as ``parts`` partial counts summed."""
    bounds = np.linspace(0, src.size, parts + 1).astype(np.int64)
    partial = pool.map(lambda i: np.bincount(src[bounds[i]:bounds[i + 1]],
                                             minlength=n),
                       range(parts))
    deg = np.zeros(n, np.int64)
    for c in partial:
        deg += c
    return deg


def blocks(n: int, src: np.ndarray, dst: np.ndarray, pool) -> list:
    """One ``(ROWS, n)`` CSR matrix of edge counts per block of
    destinations (the last one shorter)."""
    n_blocks = -(-n // ROWS)
    starts = range(0, src.size, CHUNK)

    def group(c0):
        key = (dst[c0:c0 + CHUNK] >> 16).astype(np.uint16)
        order = np.argsort(key, kind="stable")
        cut = np.concatenate(([0], np.cumsum(
            np.bincount(key, minlength=n_blocks))))
        return src[c0:c0 + CHUNK][order], dst[c0:c0 + CHUNK][order], cut

    chunks = list(pool.map(group, starts))
    sizes = sum(np.diff(c[2]) for c in chunks) if chunks else [0]
    ones = np.ones(int(np.max(sizes)), np.int8)  # every block's data

    def block(b):
        s = np.concatenate([c[0][c[2][b]:c[2][b + 1]] for c in chunks])
        rows = np.concatenate([c[1][c[2][b]:c[2][b + 1]] for c in chunks])
        rows = (rows & (ROWS - 1)).astype(np.uint16)
        order = np.argsort(rows, kind="stable")
        height = min(ROWS, n - b * ROWS)
        # int32 row pointers keep scipy's indices int32 (4 B an edge)
        indptr = np.concatenate(([0], np.cumsum(
            np.bincount(rows, minlength=height)))).astype(
                np.int32 if s.size < 2**31 else np.int64)
        return sp.csr_matrix((ones[:s.size], s[order], indptr),
                             shape=(height, n))

    return list(pool.map(block, range(n_blocks)))


def pagerank(n: int, src: np.ndarray, dst: np.ndarray, d: float,
             iterations: int, workers: int | None = None) -> np.ndarray:
    """``(n,)`` float64 ranks after ``iterations`` iterations."""
    workers = _workers(workers)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        deg = out_degree(n, src, pool, min(workers, 4))
        inv = np.zeros(n)
        np.divide(1.0, deg, out=inv, where=deg > 0)
        dangling = deg == 0
        del deg
        mats = blocks(n, src, dst, pool)
        x = np.full(n, 1.0 / n)
        for _ in range(iterations):
            y = x * inv
            base = (1.0 - d) / n + d * x[dangling].sum() / n
            new = np.empty(n)

            def one(b):
                lo = b * ROWS
                part = new[lo:lo + mats[b].shape[0]]
                part[:] = mats[b] @ y
                part *= d
                part += base

            list(pool.map(one, range(len(mats))))
            x = new
    return x


def compare(got: np.ndarray, exact: np.ndarray) -> tuple[float, float]:
    """``(rel_max, l1)``: the largest per-vertex ``|x − r| / r`` and the L1
    distance (every reference rank is at least ``(1 − d)/n > 0``)."""
    got = np.asarray(got, np.float64)
    if got.shape != exact.shape:
        return float("inf"), float("inf")
    diff = np.abs(got - exact)
    return float(np.max(diff / exact)), float(diff.sum())
