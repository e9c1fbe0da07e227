"""The work a PageRank sweep has to do, and the least time the chip needs
for it.

A function of the graph's size and the number of rank rows alone, never of
the tiles, caps or blocks of a layout, so the roofline share reads the same
work whatever implements the sweep.  Per sweep:

* 8 B per edge: its two int32 endpoints, read once;
* per vertex, each row's float32 rank read once and written once, and the
  vertex's float32 inverse out-degree read once;
* 2 FLOP per edge and row: one multiply, one add.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def sweep_bytes(n: int, m: int, rows: int) -> int:
    return 8 * m + n * (8 * rows + 4)


def sweep_flops(n: int, m: int, rows: int) -> int:
    return 2 * rows * m


def sweep_bound(n: int, m: int, rows: int, device_kind: str
                ) -> tuple[float, str]:
    """``(seconds, "memory" | "compute")``: the larger of bytes over HBM
    bandwidth and FLOPs over peak, and which of the two it is."""
    p = peaks(device_kind)
    t_mem = sweep_bytes(n, m, rows) / p["hbm_bytes_per_s"]
    t_flop = sweep_flops(n, m, rows) / p["flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")


def roofline_share(run, trace):
    """The per-sweep roofline share of a traced run (``None`` without a
    device trace): required-work time per sweep over busy time per sweep,
    in %, with the bound that sets it."""
    f = run.facts
    if trace is None or trace.busy_s <= 0 or not f.get("sweeps"):
        return None
    bound, which = sweep_bound(f["n"], f["m"], f["rows"], run.device_kind)
    return {"value": 100.0 * bound * f["sweeps"] / trace.busy_s,
            "bound": which}
