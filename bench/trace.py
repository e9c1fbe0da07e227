"""Reduce a profiler trace to device busy and idle time.

The benchmark wraps its window in a ``window`` span and each call into the
program in a span of its own (``jax.profiler.TraceAnnotation``).  From the
``.xplane.pb`` the profiler writes, this module takes

* the device operations: events of the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane;
* the benchmark's spans: host events whose names the caller lists;

and reduces them, inside the window, to the busy time (the union of the
operations' intervals, averaged over the chips), the time per operation
name, and the idle gaps, each attributed to the span the host was in.

    python3 bench/trace.py <file.xplane.pb>     # what a trace holds
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys
from collections import Counter, defaultdict

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_LINE = "XLA Ops"
WINDOW_SPAN = "window"
NO_SPAN = "no_span"  # an idle gap during which the host was in no span

Event = tuple[str, int, int]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # union of device-op intervals, mean over chips
    op_s: dict  # op name -> summed device seconds, all chips
    gaps: list  # (span name, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def load(path: str, span_names) -> tuple[dict, list]:
    """``({chip plane: [Event]}, [Event])``: the device operations of each
    chip, and the host events named in ``span_names`` (``window`` always)."""
    from jax.profiler import ProfileData

    names = set(span_names) | {WINDOW_SPAN}
    device: dict[str, list] = defaultdict(list)
    host: list = []
    for plane in ProfileData.from_file(path).planes:
        on_chip = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if on_chip and line.name != DEVICE_LINE:
                continue
            for e in line.events:
                name = op_name(e.name) if on_chip else e.name
                ev = (name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                if on_chip:
                    device[plane.name].append(ev)
                elif e.name in names:
                    host.append(ev)
    return dict(device), host


def op_name(hlo: str) -> str:
    """The operation's name from the HLO text a TPU trace gives as an event's
    name: ``"%fusion.3 = f32[8]{0} fusion(...)"`` -> ``"fusion.3"``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ev: Event, lo: int, hi: int):
    s, e = max(ev[1], lo), min(ev[2], hi)
    return (s, e) if e > s else None


def reduce(device: dict, host: list) -> Reduction:
    """Reduce one trace (see :func:`load`) over its ``window`` span."""
    windows = [ev for ev in host if ev[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    _, lo, hi = windows[0]
    spans = [ev for ev in host if ev[0] != WINDOW_SPAN]
    op_s: Counter = Counter()
    busy_ns = 0
    gaps = []
    for events in device.values():
        clipped = []
        for ev in events:
            c = _clip(ev, lo, hi)
            if c:
                clipped.append(c)
                op_s[ev[0]] += (c[1] - c[0]) * 1e-9
        busy = _union(clipped)
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_span_at(spans, s, e), (e - s) * 1e-9))
    chips = max(len(device), 1)
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / chips,
                     op_s=dict(op_s), gaps=gaps)


def _span_at(spans, s: int, e: int) -> str:
    """The span that covers most of ``[s, e)``; ``no_span`` where the host
    was in no span for longer than in any one of them."""
    best, best_ns = NO_SPAN, 0
    covered = 0
    for name, a, b in spans:
        ov = min(b, e) - max(a, s)
        if ov > 0:
            covered += ov
            if ov > best_ns:
                best, best_ns = name, ov
    return best if best_ns >= (e - s) - covered else NO_SPAN


def main(path: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = Counter(e.name for e in events).most_common(5)
            first = events[0].start_ns if events else None
            print(f"  line {line.name!r}: {len(events)} events, first at "
                  f"{first} ns, top {names}")


if __name__ == "__main__":
    main(sys.argv[1])
