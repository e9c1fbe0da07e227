"""Chip smoke test: global PageRank and PPR serving on one TPU, each checked
against the float64 oracle.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the distributed variants only

Phase A solves the full-size webStanford surrogate with ``pallas_nosync``,
``pallas`` and the jnp ``barrier`` through the variant registry (the calls
the launcher makes), and asserts that the Pallas solves lower to a TPU
kernel.  Phase B serves 32 mixed PPR queries on the full-size socEpinions1
surrogate with the Pallas serving backend, 8 slots.  ``--chips 4`` instead
runs ``distributed_barrier`` and ``distributed_stale`` on a mesh of the four
chips, against the oracle and the one-chip jnp ``barrier``.

Everything runs in this one process (a chip belongs to one process).  The
script exits non-zero, printing no result, when JAX finds no TPU.  The last
line of stdout is ``{"ok": true, "device": {...}}``; a failed check raises.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import l1_norm, pagerank_numpy  # noqa: E402
from repro.core.solver import build_variant, get_variant  # noqa: E402
from repro.graphs import make_dataset  # noqa: E402
from repro.utils.platform import init_compile_cache  # noqa: E402

SEED = 0
# Global PageRank: stop when no rank moves by more than THRESHOLD in a
# sweep.  At 1e-10 the jnp barrier reaches an L1 of 3.4e-7 from the 1e-12
# float64 oracle on this graph in f32 (CPU rehearsal), inside L1_LIMIT.
THRESHOLD = 1e-10
L1_LIMIT = 1e-6
# Pallas layout for webStanford: the launcher's --block 1024 --tile-cap 128
# give 76,371 tiles (39 MB per tile stream); the defaults (256, 1024) give
# 863,463 tiles, whose tile->block maps overflow the kernel's 1 MiB of SMEM.
PALLAS_LAYOUT = dict(block=1024, tile_cap=128)
# PPR serving: the launcher's `serve` defaults, and the top-k check of
# scripts/check.sh with a band that covers the serving threshold.
SERVE_THRESHOLD = 1e-6
SERVE_TOL = 1e-5
TOP_K = 10
CHECKED_ANSWERS = 4

# seconds JAX spent tracing, lowering and compiling, summed over the process
_compile_s = [0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if event.startswith("/jax/core/compile/"):
        _compile_s[0] += duration


def _arrays(bundle) -> dict:
    items = bundle._asdict() if hasattr(bundle, "_asdict") else vars(bundle)
    return {k: x for k, x in items.items() if isinstance(x, jax.Array)}


def _with_arrays(bundle, arrays: dict):
    if hasattr(bundle, "_replace"):
        return bundle._replace(**arrays)
    return dataclasses.replace(bundle, **arrays)


def _nbytes(bundle) -> int:
    return sum(x.nbytes for x in _arrays(bundle).values())


def solve_on_device(variant, bundle, **run_opts):
    """Lower, compile and run one registry solve with the bundle's arrays as
    arguments.  Returns ``(lowered text, compile s, solve wall s, result)``."""
    arrays = _arrays(bundle)
    lowered = jax.jit(
        lambda a: variant.run(_with_arrays(bundle, a), **run_opts)
    ).lower(arrays)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    result = jax.block_until_ready(compiled(arrays))
    return lowered.as_text(), compile_s, time.perf_counter() - t0, result


def _graph(name: str, scale_down: float):
    t0 = time.perf_counter()
    g = make_dataset(name, scale_down=scale_down, seed=SEED)
    ref, it_ref = pagerank_numpy(g, threshold=1e-12)
    print(f"{name}: n={g.n} m={g.m}  oracle: {it_ref} iterations at 1e-12, "
          f"graph+oracle {time.perf_counter() - t0:.3f}s", flush=True)
    return g, ref


def phase_pagerank(scale_down: float = 1.0) -> None:
    """Phase A: global PageRank on webStanford through the registry."""
    g, ref = _graph("webStanford", scale_down)
    bundles = {}
    for name in ("pallas_nosync", "pallas", "barrier"):
        v = get_variant(name)
        t0 = time.perf_counter()
        if v.layout not in bundles:
            bundles[v.layout] = v.build(g, **PALLAS_LAYOUT)
        bundle = bundles[v.layout]
        build_s = time.perf_counter() - t0
        text, compile_s, wall, r = solve_on_device(v, bundle, threshold=THRESHOLD)
        l1 = l1_norm(np.asarray(r.pr), ref)
        tiles = (f"tiles={bundle.tiles_src_local.shape[0]}"
                 if v.backend == "pallas" else f"edges={g.m}")
        print(f"A {name}: {tiles} layout_bytes={_nbytes(bundle)} "
              f"build_s={build_s:.3f} compile_s={compile_s:.3f} "
              f"solve_wall_s={wall:.3f} iterations={int(r.iterations)} "
              f"l1={l1:.3e}", flush=True)
        if v.backend == "pallas":
            assert "tpu_custom_call" in text, f"{name} lowered without a TPU kernel"
        assert np.isfinite(np.asarray(r.pr)).all(), name
        assert l1 < L1_LIMIT, f"{name}: L1 {l1:.3e} >= {L1_LIMIT}"


def phase_serving(scale_down: float = 1.0, queries: int = 32) -> None:
    """Phase B: `serve --backend pallas --slots 8` on socEpinions1."""
    from repro.ppr import ppr_numpy, teleport_from_seeds
    from repro.serving.ppr_engine import PPREngine, PPRQuery, make_query_stream
    from repro.serving.runtime import ServingRuntime

    g = make_dataset("socEpinions1", scale_down=scale_down, seed=SEED)
    t0 = time.perf_counter()
    eng = PPREngine(g, slots=8, threshold=SERVE_THRESHOLD, backend="pallas")
    build_s = time.perf_counter() - t0
    pg = eng._backend.pg
    c0, t0 = _compile_s[0], time.perf_counter()
    ServingRuntime(eng).serve([PPRQuery(qid=-1, seeds=(), top_k=TOP_K)])
    first_s, first_compile_s = time.perf_counter() - t0, _compile_s[0] - c0
    eng.reset()  # the measured stream starts with a cold warm-start cache

    stream = make_query_stream(g.n, queries, top_k=TOP_K, seed=SEED)
    runtime = ServingRuntime(eng, queue_depth=queries)
    c0, t0 = _compile_s[0], time.perf_counter()
    responses = runtime.serve(stream)
    wall, stream_compile_s = time.perf_counter() - t0, _compile_s[0] - c0
    assert sorted(r.qid for r in responses) == list(range(queries)), "lost queries"
    iters = [r.iterations for r in responses]
    print(f"B serve socEpinions1: n={g.n} m={g.m} tiles={pg.layout.tiles} "
          f"pad_tiles={pg.layout.pad_tiles} "
          f"layout_bytes={_nbytes(pg)} build_s={build_s:.3f} "
          f"first_query_s={first_s:.3f} (compile_s={first_compile_s:.3f}) "
          f"serve_wall_s={wall:.3f} (compile_s={stream_compile_s:.3f}) "
          f"queries={len(responses)} "
          f"iterations min/max={min(iters)}/{max(iters)}", flush=True)

    solved = [r for r in sorted(responses, key=lambda r: r.qid) if not r.cached]
    checked = solved[:CHECKED_ANSWERS]
    ref_rows, _ = ppr_numpy(g, teleport_from_seeds([r.seeds for r in checked], g.n),
                            threshold=1e-12)
    worst = 0.0
    for r, ref in zip(checked, ref_rows):
        kth = np.sort(ref)[::-1][r.indices.size - 1]
        # every answered vertex ranks within the oracle's top-k band, and
        # its score matches the oracle's
        assert (ref[r.indices] >= kth - SERVE_TOL).all(), (r.qid, r.seeds)
        err = float(np.abs(r.values - ref[r.indices]).max())
        assert err < SERVE_TOL, (r.qid, r.seeds, err)
        worst = max(worst, err)
    print(f"B oracle check: {len(checked)} answers match ppr_numpy top-{TOP_K}, "
          f"max |value error|={worst:.3e}", flush=True)
    assert len(checked) >= CHECKED_ANSWERS


def phase_distributed(chips: int, scale_down: float = 1.0) -> None:
    """The mesh path: distributed_barrier and distributed_stale on all chips,
    against the oracle and the one-chip jnp barrier."""
    g, ref = _graph("webStanford", scale_down)
    v = get_variant("barrier")
    _, _, wall, one = solve_on_device(v, v.build(g), threshold=THRESHOLD)
    one_pr = np.asarray(one.pr)
    print(f"C barrier (one device): iterations={int(one.iterations)} "
          f"solve_wall_s={wall:.3f} l1={l1_norm(one_pr, ref):.3e}", flush=True)
    for name in ("distributed_barrier", "distributed_stale"):
        v, bundle = build_variant(name, g, threads=chips)
        assert bundle.mesh.devices.size == chips, bundle.mesh
        t0 = time.perf_counter()
        r = jax.block_until_ready(v.run(bundle, threshold=THRESHOLD))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = jax.block_until_ready(v.run(bundle, threshold=THRESHOLD))
        wall = time.perf_counter() - t0
        pr = np.asarray(r.pr)
        l1, l1_one = l1_norm(pr, ref), l1_norm(pr, one_pr)
        print(f"C {name}: mesh={bundle.mesh.devices.size} rounds={int(r.iterations)} "
              f"first_s(compile included)={first_s:.3f} solve_wall_s={wall:.3f} "
              f"l1={l1:.3e} l1_vs_one_device_barrier={l1_one:.3e}", flush=True)
        assert l1 < L1_LIMIT, f"{name}: L1 {l1:.3e} >= {L1_LIMIT}"
        assert l1_one < 2 * L1_LIMIT, f"{name}: L1 vs barrier {l1_one:.3e}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed variants on a 4-chip mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    init_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    print(f"device: {devices[0].device_kind} x{len(devices)}  jax {jax.__version__}",
          flush=True)
    if args.chips == 4:
        phase_distributed(args.chips)
    else:
        phase_pagerank()
        phase_serving()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
