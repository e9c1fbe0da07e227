"""The harness's core: a cell's specification from ``BENCHMARK.json``, the
:class:`Run` a traffic driver works through, its :class:`Outcome`, and the
result line.  ``bench/run.py`` is the command around it."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def load_module(path: Path):
    """Import a file of the benchmark by its path (metric files carry dots
    in their names)."""
    name = "bench._files." + path.stem.replace(".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def cell_spec(workload: str) -> dict:
    """The cell's entry, its configuration, its mix, and the metrics that
    ``BENCHMARK.json`` asks of it."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def asked(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {
        "cell": cell,
        "config": json.loads((ROOT / cfg_entry["file"]).read_text()),
        "mix": json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                          .read_text()),
        "end_to_end": asked(spec["end_to_end"]),
        "per_layer": asked(spec["per_layer"]),
    }


class Run:
    """What a driver sees of the harness: the cell's parameters, the
    window's bounds, spans, and a ``facts`` dict that metric readers read.

    A driver builds the graph and the program, warms up, then calls
    :meth:`open_window` and :meth:`close_window` around the measured
    window, :meth:`note_memory` once the window's work has ended, and
    returns a :class:`Outcome`.
    """

    def __init__(self, *, config: dict, mix: dict, seed: int, seconds: float,
                 trace: bool, device_kind: str, name: str = "run",
                 memory=lambda: None, t_start: float | None = None):
        self.config, self.mix, self.seed = config, mix, seed
        self.seconds, self.trace, self.device_kind = seconds, trace, device_kind
        self.name = name
        self.facts: dict = {}
        self.compile_log: list = []  # (event, seconds, perf_counter at end)
        self._memory = memory
        self._window = None
        self._t_start = time.perf_counter() if t_start is None else t_start
        self.trace_dir = TRACE_DIR / f"{name}.{seed}"

    def on_compile_event(self, event: str, duration: float, **_) -> None:
        if event.startswith(COMPILE_EVENT):
            self.compile_log.append((event, duration, time.perf_counter()))

    def span(self, name: str):
        """A host span in the trace (nothing when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def timed(self, fact: str):
        """Add the host-clock seconds of the block to ``facts[fact]``."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.facts[fact] = self.facts.get(fact, 0.0) + (
                time.perf_counter() - t0)

    def open_window(self) -> float:
        """End set-up, start the trace if asked; returns the window's t0."""
        now = time.perf_counter()
        self.facts["setup_s"] = now - self._t_start
        self.facts["compile_s"] = sum(d for _, d, _ in self.compile_log)
        self._compiles_before = len(self.compile_log)
        if self.trace:
            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # no Python function tracing: it would slow the host and swell
            # the file
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._window = self.span("window")
            self._window.__enter__()
        return time.perf_counter()

    def close_window(self) -> None:
        if self._window is not None:
            import jax
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.facts["compiles_in_window"] = sum(
            e == BACKEND_COMPILE for e, _, _ in
            self.compile_log[self._compiles_before:])

    def note_memory(self) -> None:
        self.facts["memory_peak_bytes"] = self._memory()


class Outcome:
    """A driver's result: end-to-end values, the numbers compared with
    their limits (``(name, value, limit)``: correct when value <= limit),
    and the attempted and failed counts."""

    def __init__(self, end_to_end: dict, checks: list, attempted: int,
                 failed: int):
        self.end_to_end, self.checks = end_to_end, checks
        self.attempted, self.failed = attempted, failed

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.checks)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             device_kind: str, memory=lambda: None, name: str = "run",
             t_start: float | None = None):
    """Drive the cell and return ``(run, outcome)``; touches no chip check,
    so a test can drive it on the CPU."""
    import jax

    mix = spec["mix"]
    driver = load_module(BENCH / "traffic" / f"{mix['driver']}.py")
    run = Run(config=spec["config"], mix=mix, seed=seed, seconds=seconds,
              trace=trace, device_kind=device_kind, name=name, memory=memory,
              t_start=t_start)
    jax.monitoring.register_event_duration_secs_listener(run.on_compile_event)
    try:
        outcome = driver.run(run)
    finally:
        jax.monitoring.unregister_event_duration_listener(run.on_compile_event)
    return run, outcome


def reader(metric: str):
    """The module that reads ``metric``: ``bench/metrics/<metric>.py``, or
    else the file of the name's first part, so that one reader serves
    ``device_idle.solve`` and ``device_idle.ppr_steady`` alike."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{metric.split('.', 1)[0]}.py"
    return load_module(path)


def layer_metrics(spec: dict, run: Run):
    """Per-layer metrics of the traced run, and the trace's reduction.  A
    trace with no operation on a chip is an error: the device metrics
    would be silently missing."""
    from bench import trace as tr

    device, host = tr.load(tr.find_xplane(str(run.trace_dir)),
                           run.mix.get("spans", []))
    if not any(device.values()):
        raise RuntimeError(f"the trace under {run.trace_dir} holds no "
                           f"operation on a {tr.DEVICE_PLANE_PREFIX} plane")
    red = tr.reduce(device, host)
    out = {}
    for m in spec["per_layer"]:
        value = reader(m["name"]).read(run, red)
        if value is None:
            continue
        entry = value if isinstance(value, dict) else {"value": value}
        out[m["name"]] = {**entry, "unit": m["unit"]}
    return out, red


def result_line(spec: dict, run: Run, outcome: Outcome, devices) -> dict:
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.facts.get("memory_peak_bytes")}
    line = {"correct": outcome.correct, "attempted": outcome.attempted,
            "failed": outcome.failed}
    if run.trace:
        metrics, red = layer_metrics(spec, run)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        line.update(metrics=metrics, device=device, breakdown=red.breakdown())
    else:
        values = {**outcome.end_to_end, "setup_s": run.facts["setup_s"]}
        line.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]}, device=device)
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, v, lim in outcome.checks}
    return line
