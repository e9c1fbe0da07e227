"""Stale-sync (No-Sync on TPU) vs barrier vs top-k exchange: traffic & rounds.

Runs in the calling process on the devices present, one shard per device
(a chip belongs to one process, so no child may open it).  Drives the
*registry* entries (``distributed_barrier`` / ``distributed_stale`` /
``distributed_topk``) — the same path the launcher and round-trip tests use
— and measures real rounds-to-converge, real wall time, and the derived
collective-bytes-per-solve reduction (the pod-scale win of the paper's idea:
exchange frequency ÷ local_sweeps at equal fixed point, and top-k delta
publishing beyond it).  A failed solve raises.
"""
from __future__ import annotations

import time

import jax

from benchmarks.common import csv_row

RUNS = (
    ("barrier_k1", "distributed_barrier", dict(local_sweeps=1)),
    ("stale_k2", "distributed_stale", dict(local_sweeps=2)),
    ("stale_k4", "distributed_stale", dict(local_sweeps=4)),
    ("stale_k8", "distributed_stale", dict(local_sweeps=8)),
    ("topk_f8", "distributed_topk", dict(local_sweeps=2, send_fraction=0.125)),
)
SCALE_DOWN = 64


def bench() -> dict:
    from repro.core import l1_norm, pagerank_numpy
    from repro.core.solver import build_variant, get_variant
    from repro.graphs import make_dataset

    g = make_dataset("webStanford", scale_down=SCALE_DOWN)
    ref, _ = pagerank_numpy(g, threshold=1e-12)
    p = jax.device_count()
    vp = -(-g.n // p)
    n_pad = vp * p
    out = {"n": g.n, "m": g.m, "devices": p}
    # one shared bundle (all three variants have layout="distributed"); the
    # timed region is the solve only, not the host-side partitioning/mesh build
    _, bundle = build_variant("distributed_barrier", g, threads=p)
    for key, variant, opts in RUNS:
        v = get_variant(variant)
        t0 = time.perf_counter()
        r = jax.block_until_ready(v.run(bundle, threshold=1e-7, **opts))
        wall = time.perf_counter() - t0
        rounds = int(r.iterations)
        if variant == "distributed_topk":
            # each round publishes k index+value pairs per shard (8B each)
            k = max(1, int(vp * opts["send_fraction"]))
            coll = rounds * p * k * 8
        else:
            # each round all-gathers the rank vector: bytes = rounds * n_pad * 4
            coll = rounds * n_pad * 4
        out[key] = {"rounds": rounds, "wall_s": wall,
                    "coll_bytes": coll, "l1": l1_norm(r.pr, ref)}
    return out


def main() -> list[str]:
    out = bench()
    rows = []
    base = out["barrier_k1"]
    for key, _, _ in RUNS:
        d = out[key]
        rows.append(csv_row(
            f"dist/{key}", d["wall_s"] * 1e6,
            f"devices={out['devices']};rounds={d['rounds']};"
            f"coll_bytes={d['coll_bytes']};"
            f"coll_reduction={base['coll_bytes']/max(d['coll_bytes'],1):.2f}x;l1={d['l1']:.1e}",
        ))
    return rows


if __name__ == "__main__":
    print("\n".join(main()))
