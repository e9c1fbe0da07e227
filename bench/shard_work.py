"""The work one shard's round has to do, and where the device time of a
sharded run goes: busy time and collectives per chip.

Required bytes of shard ``i``'s round (a function of the graph's size and
the shard's edge count alone, not of the program's layout):

* 8 B per real edge of the shard: its two int32 endpoints, read once;
* 4 B per vertex of the padded graph for each of the gathered rank
  vector, the inverse out-degrees and the dangling mask, read once;
* 4 B per vertex the shard owns, its new rank written once.

The least time is those bytes over the chip's HBM bandwidth
(``bench/peaks.json``); the time compared with it is the chip's busy time
in the window less its collectives, per round.

A collective is an operation of a chip's ``XLA Ops`` line whose HLO name
or text names an all-gather, all-reduce, collective-permute,
reduce-scatter or all-to-all (the ``-start`` and ``-done`` halves of an
asynchronous one included); the program's ``dist.exchange`` named scope is
not needed to find them.
"""
from __future__ import annotations

import dataclasses
import functools
import re

from bench.trace import (DEVICE_LINE, DEVICE_PLANE_PREFIX, WINDOW_SPAN,
                         _clip, _union, find_xplane, op_name)
from bench.work import peaks

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all")


def round_bytes(n_pad: int, vp: int, edges: int) -> int:
    return 8 * edges + 3 * 4 * n_pad + 4 * vp


def is_collective(name: str, hlo: str = "") -> bool:
    """Whether an operation is a collective, by its HLO name (``all-
    gather-start.2``) or the operation kind in its HLO text."""
    if COLLECTIVE.match(name):
        return True
    _, _, rest = hlo.partition(" = ")
    return bool(re.search(r"\s(?:%s)(?:-start|-done)?\(" % COLLECTIVE.pattern,
                          rest))


@dataclasses.dataclass
class ChipTime:
    """One chip's seconds inside the window: busy (the union of its
    operations) and in collectives (the union of those)."""

    busy_s: float
    collective_s: float


def chip_times(ops: dict, window: tuple[int, int]) -> dict:
    """``{device id: ChipTime}`` from ``{plane name: [(name, hlo, start,
    end)]}`` and the window's ``(start, end)`` in ns."""
    lo, hi = window
    out = {}
    for plane, events in ops.items():
        busy, coll = [], []
        for name, hlo, s, e in events:
            c = _clip((name, s, e), lo, hi)
            if c:
                busy.append(c)
                if is_collective(name, hlo):
                    coll.append(c)
        dev = int(re.match(r"\d+", plane[len(DEVICE_PLANE_PREFIX):]).group())
        out[dev] = ChipTime(
            busy_s=1e-9 * sum(e - s for s, e in _union(busy)),
            collective_s=1e-9 * sum(e - s for s, e in _union(coll)))
    return out


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """Per-chip :class:`ChipTime` of a trace file, inside its window."""
    from jax.profiler import ProfileData

    ops, window = {}, None
    for plane in ProfileData.from_file(path).planes:
        on_chip = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if on_chip and line.name == DEVICE_LINE:
                ops.setdefault(plane.name, []).extend(
                    (op_name(e.name), e.name, int(e.start_ns),
                     int(e.start_ns + e.duration_ns)) for e in line.events)
            elif not on_chip:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return chip_times(ops, window)


def of_run(run):
    """The per-chip times of a traced run; None without a trace."""
    if not run.trace:
        return None
    return load(find_xplane(str(run.trace_dir)))


def layout():
    """The program's ``LAYOUTS["distributed"]`` counter; None from a
    program that keeps none."""
    try:
        from repro.utils.tracing import LAYOUTS
    except ImportError:
        return None
    return LAYOUTS.get("distributed")


def shares(run):
    """Per-round numbers of a traced sharded run, or None where it has no
    trace, rounds or layout: ``round_ms`` (busy ms per round, mean over
    chips), ``exchange`` (% of busy time in collectives, all chips) and
    ``roofline`` (per device id: required bytes over HBM bandwidth, over
    the busy time less the collectives per round, in %)."""
    chips, lay, rounds = of_run(run), layout(), run.facts.get("rounds")
    if not chips or not rounds or not lay:
        return None
    busy = sum(c.busy_s for c in chips.values())
    coll = sum(c.collective_s for c in chips.values())
    bw = peaks(run.device_kind)["hbm_bytes_per_s"]
    edges = dict(zip(run.facts.get("shard_devices", []),
                     lay["edges_per_shard"]))
    roof = {}
    for dev, c in sorted(chips.items()):
        compute_s = (c.busy_s - c.collective_s) / rounds
        if dev in edges and compute_s > 0:
            need = round_bytes(lay["n_pad"], lay["vp"], edges[dev])
            roof[dev] = 100.0 * need / bw / compute_s
    return {"round_ms": 1e3 * busy / len(chips) / rounds,
            "exchange": 100.0 * coll / busy if busy else None,
            "roofline": roof}
