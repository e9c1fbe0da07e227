"""Device time of one grid step of the sweep's Pallas kernel: the seconds
of the window's operations named after the kernel (``spmv_gs_pass.11``),
over the window's sweeps (one kernel call each) times the kernel's grid
steps per call, which the program records as it traces the kernel
(``repro.utils.tracing.GRID_STEPS``).  Reads every ``tile_step_us.<cell
kind>`` metric; the kernel is the recorded one with the most device time."""
import re


def read(run, trace):
    try:
        from repro.utils.tracing import GRID_STEPS
    except ImportError:  # a program that records no grid steps
        return None
    return value(trace.op_s, run.facts.get("sweeps"), GRID_STEPS)


def value(op_s, sweeps, grid_steps):
    seconds = {}
    for name, s in op_s.items():
        kernel = re.sub(r"\.\d+$", "", name)
        if kernel in grid_steps:
            seconds[kernel] = seconds.get(kernel, 0.0) + s
    if not sweeps or not seconds:
        return None
    kernel = max(seconds, key=seconds.get)
    steps = grid_steps[kernel]
    return {"value": 1e6 * seconds[kernel] / (sweeps * steps),
            "grid_steps": steps, "kernel": kernel}
