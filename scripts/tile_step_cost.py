"""Per-grid-step cost of the SpMV Gauss–Seidel kernels against the tile
layout, on the chip.

    python3 scripts/tile_step_cost.py --scale 16 --seed 0 \\
        --layouts 256/1024,256/128,512/128,1024/128,1024/256

Draws the Graph500 Kronecker graph of ``--scale`` from ``--seed``
(``bench/graph.py``), builds each ``block/tile_cap`` layout, and times
back-to-back calls of ``spmv_gs_pass_multi`` at ``--rows`` rows and of
``spmv_gs_pass`` (one row), with the layout's arrays as arguments.  A call's
time over its grid steps is the cost of one grid step.  Prints one JSON line
per layout and kernel, then the least-squares fit
``us_per_step = c0 + c1 · block · cap`` over the lane-dense layouts (cap a
multiple of 128).  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import graph  # noqa: E402
from repro.graphs.csr import Graph  # noqa: E402
from repro.kernels.spmv.kernel import (  # noqa: E402
    MAX_TILES,
    spmv_gs_pass,
    spmv_gs_pass_multi,
)
from repro.kernels.spmv.ops import PallasGraph  # noqa: E402


def _seconds_per_call(call, x) -> float:
    """Best of three timed runs of back-to-back calls, each run at least
    half a second, after one compiling call."""
    x = jax.block_until_ready(call(x))
    t = time.perf_counter()
    x = jax.block_until_ready(call(x))
    reps = min(200, max(3, math.ceil(0.5 / (time.perf_counter() - t))))
    best = math.inf
    for _ in range(3):
        t = time.perf_counter()
        y = x
        for _ in range(reps):
            y = call(y)
        jax.block_until_ready(y)
        best = min(best, (time.perf_counter() - t) / reps)
    return best


def measure(g: Graph, block: int, cap: int, rows: int) -> list[dict]:
    t = time.perf_counter()
    pg = PallasGraph.build(g, block=block, tile_cap=cap)
    build_s = time.perf_counter() - t
    tiles = (pg.tiles_src_local, pg.tiles_dst_local, pg.tiles_valid,
             pg.tiles_valid, pg.tile_src_block, pg.tile_dst_block)
    n_tiles = int(pg.tiles_src_local.shape[0])
    if n_tiles > MAX_TILES:  # the tile maps would overflow SMEM
        return [dict(block=block, tile_cap=cap, tiles=n_tiles, skipped=True)]
    nb = pg.n_blocks
    vmask = (jnp.arange(nb * block) < g.n).astype(jnp.float32).reshape(nb, block)
    common = dict(block=block, tile_cap=cap, tiles=n_tiles,
                  fill=g.m / (n_tiles * cap), build_s=build_s)

    multi = jnp.full((nb, rows, block), 1.0 / g.n, jnp.float32)
    base = jnp.full((nb, rows, block), 0.15 / g.n, jnp.float32)
    frozen = jnp.zeros((1, rows), jnp.float32)
    d = jnp.asarray([[0.85]], jnp.float32)
    s_multi = _seconds_per_call(
        lambda pr: spmv_gs_pass_multi(pr, pg.inv_out_blocks, vmask, frozen,
                                      base, d, *tiles, block=block), multi)

    single = jnp.full((nb, block), 1.0 / g.n, jnp.float32)
    params = jnp.asarray([[0.15 / g.n, 0.85, 0.0]], jnp.float32)
    zeros = jnp.zeros_like(vmask)
    s_single = _seconds_per_call(
        lambda pr: spmv_gs_pass(pr, pg.inv_out_blocks, vmask, vmask, zeros,
                                params, *tiles, block=block), single)
    return [dict(common, kernel="spmv_gs_pass_multi", rows=rows,
                 sweep_ms=1e3 * s_multi, us_per_step=1e6 * s_multi / n_tiles),
            dict(common, kernel="spmv_gs_pass", rows=1,
                 sweep_ms=1e3 * s_single, us_per_step=1e6 * s_single / n_tiles)]


def fit(points: list[dict]) -> dict:
    """Least squares of ``us_per_step`` on ``[1, block·cap]``."""
    area = np.asarray([p["block"] * p["tile_cap"] for p in points], float)
    us = np.asarray([p["us_per_step"] for p in points])
    (c0, c1), *_ = np.linalg.lstsq(np.stack([np.ones_like(area), area], 1),
                                   us, rcond=None)
    pred = c0 + c1 * area
    return {"c0_us": float(c0), "c1_us_per_elem": float(c1),
            "worst_rel_residual": float(np.max(np.abs(pred - us) / us)),
            "layouts": len(points)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--layouts", default="256/1024,256/128,512/128,1024/128,"
                    "1024/256,2048/1024")
    ap.add_argument("--out", default=None,
                    help="also write the JSON lines to this file")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("tile_step_cost: no TPU found", file=sys.stderr)
        return 1
    n, src, dst = graph.edges(
        {"scale": args.scale, "edgefactor": 16,
         "initiator": [0.57, 0.19, 0.19]}, args.seed)
    g = Graph.from_edges(n, src, dst)
    lines = []
    for spec in args.layouts.split(","):
        block, cap = (int(x) for x in spec.split("/"))
        for rec in measure(g, block, cap, args.rows):
            rec.update(scale=args.scale, seed=args.seed,
                       device=jax.devices()[0].device_kind)
            print(json.dumps(rec), flush=True)
            lines.append(rec)
    for kernel in ("spmv_gs_pass_multi", "spmv_gs_pass"):
        dense = [p for p in lines if p.get("kernel") == kernel
                 and p["tile_cap"] % 128 == 0]
        if len(dense) >= 2:
            rec = dict(fit(dense), kernel=kernel, fit=True)
            print(json.dumps(rec), flush=True)
            lines.append(rec)
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text("".join(json.dumps(r) + "\n" for r in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
