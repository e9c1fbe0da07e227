"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``: it names a
configuration (``configs[].file``, the deployment's sizes, guarantee and
limits) and a traffic mix (``bench/traffic/<traffic>.json``, whose
``driver`` names the module ``bench/traffic/<driver>.py`` that generates
and offers it).  With ``--trace 0`` the run prints the cell's end-to-end
metrics; with ``--trace 1`` it traces the window and prints the cell's
per-layer metrics, each read by ``bench/metrics/<metric>.py`` or, where
there is no such file, by the file of the name's first part
(``device_idle.py`` reads ``device_idle.solve``).  Adding a configuration,
a mix or a metric is adding files and entries.

The last line on stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with its limit); the checks are also
the last lines on stderr.  Without a TPU, or with fewer chips than the cell
asks for, the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import cell_spec, result_line, run_cell

    spec = cell_spec(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < spec["cell"]["chips"]:
        print(f"bench: the cell needs {spec['cell']['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    from repro.utils.platform import init_compile_cache

    init_compile_cache()
    # cache every program, however fast it compiled, so a second run of a
    # cell finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def memory():
        return max(d.memory_stats()["peak_bytes_in_use"]
                   for d in devices[:spec["cell"]["chips"]])

    run, outcome = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                            devices[0].device_kind, memory=memory,
                            name=args.workload, t_start=T_START)
    line = result_line(spec, run, outcome, devices[:spec["cell"]["chips"]])
    print(f"compiles in the window: {run.facts['compiles_in_window']}",
          file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    # the repository root (for ``bench``) and the program; not bench/
    # itself, whose module names would shadow the standard library's
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]
    raise SystemExit(main())
