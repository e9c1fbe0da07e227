"""The program's own spans in a ``--trace 1`` run, with their stats, and the
device's work per engine step.

The program marks its work with spans on the profiler's clock
(``repro.utils.tracing.span``: ``ppr.offer``, ``ppr.admit``, ``ppr.step``
with ``ppr.dispatch``, ``ppr.sync`` and ``ppr.harvest`` inside it,
``ppr.cache_insert``), each carrying its arguments as event stats.  From the
``.xplane.pb`` of a traced run this module takes, in one pass over the file:

* the program's spans that start inside the benchmark's ``window`` span,
  with their stats;
* per chip, the device's operations (``XLA Ops``) and the programs it ran
  (``XLA Modules``: one event per call of a jitted function, named
  ``jit_<function>(<fingerprint>)``).

A program that has no such spans (one older than them) gives empty lists,
and the readers in ``bench/metrics`` then report nothing.

    python3 -m bench.program_spans <trace dir>   # from the repository root
"""
from __future__ import annotations

import dataclasses
import functools
import statistics
import sys
from collections import Counter

from bench.trace import (DEVICE_LINE, DEVICE_PLANE_PREFIX, WINDOW_SPAN,
                         _clip, _union, find_xplane, op_name)

PROGRAM_PREFIX = "ppr."
MODULE_LINE = "XLA Modules"
STEP_MODULE = "jit_multi_step"  # the engine step's jitted program
NONE = "none"  # host time in no program span


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int  # ns, the profiler's clock
    end: int
    args: dict


@dataclasses.dataclass
class Trace:
    """One traced run: the program spans of the window (in start order), and
    per chip its ``(name, start_ns, end_ns)`` operations and modules."""

    spans: list
    ops: dict
    modules: dict

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


def of_run(run):
    """The :class:`Trace` of a traced run, read once per file; None for a
    run without a trace."""
    if not run.trace:
        return None
    return load(find_xplane(str(run.trace_dir)))


@functools.lru_cache(maxsize=2)
def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    host, ops, modules = [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        on_chip = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if on_chip and line.name in (DEVICE_LINE, MODULE_LINE):
                name = op_name if line.name == DEVICE_LINE else str
                out = ops if line.name == DEVICE_LINE else modules
                out.setdefault(plane.name, []).extend(
                    (name(e.name), int(e.start_ns),
                     int(e.start_ns + e.duration_ns)) for e in line.events)
            elif not on_chip:
                host += [Span(e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns), dict(e.stats))
                         for e in line.events
                         if e.name == WINDOW_SPAN
                         or e.name.startswith(PROGRAM_PREFIX)]
    return window(host, ops, modules)


def window(host: list, ops: dict, modules: dict) -> Trace:
    """The program spans that start inside the one ``window`` span."""
    windows = [s for s in host if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    spans = sorted((s for s in host if s.name != WINDOW_SPAN
                    and lo <= s.start < hi), key=lambda s: (s.start, -s.end))
    return Trace(spans, {k: sorted(v, key=lambda e: e[1])
                         for k, v in ops.items()},
                 {k: sorted(v, key=lambda e: e[1])
                  for k, v in modules.items()})


def steps(t: Trace, chip: str) -> list:
    """``(step span, dispatch span, module event)`` for each engine step of
    the window, the module being the ``jit_multi_step`` call on ``chip``
    that starts nearest the step's dispatch."""
    mods = [m for m in t.modules.get(chip, [])
            if m[0].startswith(STEP_MODULE)]
    dispatches = t.named("ppr.dispatch")
    out = []
    for st in t.named("ppr.step"):
        inner = [d for d in dispatches if st.start <= d.start < st.end]
        if not inner or not mods:
            continue
        d = inner[0]
        out.append((st, d, min(mods, key=lambda m: abs(m[1] - d.start))))
    return out


def idle_between(ops: list, lo: int, hi: int) -> list:
    """The ``[start, end)`` pieces of ``[lo, hi)`` in which no operation
    ran."""
    busy = _union(c for c in (_clip(ev, lo, hi) for ev in ops) if c)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def innermost(spans: list, lo: int, hi: int) -> Counter:
    """Nanoseconds of ``[lo, hi)`` by the innermost program span covering
    them (of those open, the latest-started, then the shortest; :data:`NONE`
    where none is)."""
    inside = [s for s in spans if s.start < hi and s.end > lo]
    cuts = sorted({lo, hi} | {x for s in inside for x in (s.start, s.end)
                              if lo < x < hi})
    out: Counter = Counter()
    for a, b in zip(cuts, cuts[1:]):
        open_ = [s for s in inside if s.start <= a and s.end >= b]
        name = max(open_, key=lambda s: (s.start, -s.end)).name \
            if open_ else NONE
        out[name] += b - a
    return out


def clock_offsets_ms(t: Trace, chip: str) -> list:
    """Per step, the start of its device program less the start of its
    ``ppr.dispatch`` on the host: launch latency plus the difference of the
    two clocks."""
    return [1e-6 * (m[1] - d.start) for _, d, m in steps(t, chip)]


def main(directory: str) -> None:
    t = load(find_xplane(directory))
    print("program spans:", dict(Counter(s.name for s in t.spans)))
    for chip in sorted(t.modules):
        off = clock_offsets_ms(t, chip)
        print(f"{chip}: {len(steps(t, chip))} steps matched to "
              f"{STEP_MODULE} calls")
        if off:
            q = statistics.quantiles(off, n=4) if len(off) > 1 else off * 3
            print(f"  device program start - dispatch start, ms: min "
                  f"{min(off):.4f} q1 {q[0]:.4f} median {q[1]:.4f} q3 "
                  f"{q[2]:.4f} max {max(off):.4f}")


if __name__ == "__main__":
    main(sys.argv[1])
