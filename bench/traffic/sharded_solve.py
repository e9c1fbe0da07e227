"""Fixed-iteration PageRank solves of a graph sharded over the cell's chips,
back to back, each from the uniform start (LDBC Graphalytics' PageRank).

Set-up draws the graph on the chips (``bench/kronecker_stream.py``),
builds the mix's registry variant from the edge list over a mesh of the
cell's ``chips`` devices (``layout_build_s``), compiles one solve with the
bundle's arrays and the round count as its operands, and warms it up with
one round (the program a full solve runs, a tenth of its chip time).  The float64
reference (``bench/sharded_reference.py``) runs on host threads from the
end of the build on, beside the device's work.  The window calls the
compiled solve until ``seconds`` have passed, each call ending in
``block_until_ready``.  Every solve of the window is checked: it ran the
configuration's ``iterations`` rounds, and no vertex's rank is further
than ``rel_max`` from the reference's, relative to it.

The mix may name a ``precision`` other than the configuration's: the
program then runs in it (the control, ``bfloat16``, which the comparison
must refuse).
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import resource
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import kronecker_stream, shard_work, sharded_reference
from bench.harness import Outcome


def _host(run, phase: str) -> None:
    """Note the host's peak resident memory so far after ``phase``, on
    stderr at once: a run ended for memory shows how far it came."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20  # GiB
    run.facts.setdefault("host_peak_gib", {})[phase] = peak
    print(f"sharded_solve: {phase} done, host peak {peak:.2f} GiB",
          file=sys.stderr, flush=True)


def run(run) -> Outcome:
    # a program without the sharded build fails here, before any work
    from repro.core.solver import get_variant
    from repro.graphs.csr import EdgeList

    cfg = run.config
    chips = int(cfg["chips"])
    dtype = jnp.dtype(run.mix.get("precision", cfg["precision"]))
    iterations, d = int(cfg["iterations"]), float(cfg["damping"])
    devices = jax.devices()[:chips]
    with run.timed("generate_s"):
        n, src, dst = kronecker_stream.edges(cfg["graph"], run.seed, devices)
    _host(run, "generate")
    variant = get_variant(run.mix["variant"])
    with run.timed("layout_build_s"):
        bundle = variant.build(EdgeList(n, src, dst), threads=chips,
                               dtype=dtype)
        arrays = jax.block_until_ready(
            {k: x for k, x in vars(bundle).items()
             if isinstance(x, jax.Array)})
    _host(run, "build")

    def reference():
        t = time.perf_counter()
        x = sharded_reference.pagerank(n, src, dst, d, iterations)
        run.facts["reference_s"] = time.perf_counter() - t
        return x

    pool = concurrent.futures.ThreadPoolExecutor(1)
    exact = pool.submit(reference)
    opts = dict(d=d, threshold=0.0, handle_dangling=cfg["handle_dangling"])
    with run.timed("aot_compile_s"):
        solve = jax.jit(lambda a, rounds: variant.run(
            dataclasses.replace(bundle, **a), max_iter=rounds, **opts)
        ).lower(arrays, iterations).compile()
    with run.timed("warmup_s"):
        jax.block_until_ready(solve(arrays, 1))
    _host(run, "warm-up")

    results = []
    t0 = run.open_window()
    while True:
        with run.span("solve"):
            results.append(jax.block_until_ready(solve(arrays, iterations)))
        elapsed = time.perf_counter() - t0
        if elapsed >= run.seconds:
            break
    run.close_window()
    run.note_memory()
    run.facts["memory_per_chip"] = [
        dev.memory_stats()["peak_bytes_in_use"]
        for dev in bundle.mesh.devices.flat
        if dev.memory_stats() is not None]

    rounds = [int(r.iterations) for r in results]
    ranks = [np.asarray(r.pr) for r in results]
    run.facts.update(n=n, m=src.size, p=bundle.p, rounds=sum(rounds),
                     solves=len(results),
                     shard_devices=[dev.id for dev in bundle.mesh.devices.flat])
    del results, solve, arrays, bundle
    exact = exact.result()
    pool.shutdown()
    _host(run, "reference")
    readings = [sharded_reference.compare(x, exact) for x in ranks]
    rel = [r for r, _ in readings]
    run.facts["l1"] = max(l1 for _, l1 in readings)
    limit = cfg["checks"]["rel_max"]
    off = sum(r != iterations for r in rounds)
    failed = sum(not (e <= limit) or r != iterations
                 for e, r in zip(rel, rounds))
    print(f"sharded_solve: l1 {run.facts['l1']!r} (for information); "
          f"solve_s {elapsed / len(ranks)!r} over {len(ranks)}; set-up "
          + " ".join(f"{k} {run.facts[k]:.3f}" for k in
                     ("setup_s", "generate_s", "layout_build_s",
                      "aot_compile_s", "warmup_s", "reference_s"))
          + f"; build {shard_work.layout()}"
          + f"; memory per chip {run.facts['memory_per_chip']}",
          file=sys.stderr)
    checks = [("rel_max", max(rel), limit), ("rounds_off", off, 0)]
    return Outcome({"solve_s": elapsed / len(ranks)}, checks,
                   attempted=len(ranks), failed=failed)
