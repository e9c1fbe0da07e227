"""Find the highest rate the PPR deployment of a cell sustains, once, by a
sweep on the chip; the cell then offers a fixed rate below it.

    python3 bench/sweep_knee.py --workload graph500-16.ppr-steady \
        --seed 5 --seconds 40 --rates 1.5 2 2.5 3 3.5 4

One process builds and compiles the deployment once, then offers the
cell's open-loop mix at each rate for ``--seconds`` and drains for up to
``--drain`` more, resetting the engine and the result cache in between.
For each rate it prints one JSON line: the answers per second inside the
window, latency from due time (p50, p90, nearest rank; a missed query
counts as waiting until the drain gave up), rejections, and the backlog
left when the window closed.  A rate is sustained while the backlog at
the close stays within a few slots and the answers keep pace with the
offers; above the knee the backlog grows all through the window.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drain", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import serving
    from bench.harness import Run, cell_spec
    from bench.traffic.ppr_open_loop import offer
    from repro.utils.platform import init_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep_knee: no TPU", file=sys.stderr)
        return 1
    init_compile_cache()
    spec = cell_spec(args.workload)
    run = Run(config=spec["config"], mix=spec["mix"], seed=args.seed,
              seconds=args.seconds, trace=False,
              device_kind=jax.devices()[0].device_kind)
    served = serving.build(run)
    print(json.dumps({"layout_build_s": run.facts["layout_build_s"]}),
          flush=True)
    for rate in args.rates:
        served.runtime.reset()
        w = offer(run, served.runtime, rate, args.drain)
        close = w.due[0] + args.seconds
        lat = w.latencies_s()
        print(json.dumps({
            "offered_qps": len(w.asked) / args.seconds,
            "answered_qps_in_window": sum(
                t <= close for t in w.harvested.values()) / args.seconds,
            "p50_ms": 1e3 * serving.nearest_rank(lat, 0.5),
            "p90_ms": 1e3 * serving.nearest_rank(lat, 0.9),
            "rejected": len(w.rejected), "lost": len(w.lost),
            "backlog_at_close": w.pending_at_close,
            "cache_hits": served.runtime.metrics.count("cache_hits"),
            "steps": serving.device_steps(served)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
    raise SystemExit(main())
