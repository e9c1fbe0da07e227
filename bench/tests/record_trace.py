"""Record the small chip trace that ``test_reduction.py`` reduces.

    python3 bench/tests/record_trace.py      # on a TPU; writes data/

Inside one ``window`` span: a ``solve`` span around two calls of a small
jitted program, then a 20 ms ``wait`` span with the chip idle, then one
more call outside any span.  The trace is copied to
``data/tpu_window.xplane.pb`` and its device events printed, for counting
by hand.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
OUT = Path(__file__).with_name("data") / "tpu_window.xplane.pb"


def main() -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp

    from bench import trace as tr
    from bench.harness import Run

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    f = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready(f(x))
    run = Run(config={}, mix={}, seed=0, seconds=0, trace=True,
              device_kind=jax.devices()[0].device_kind, name="record")
    run.trace_dir = Path(tempfile.mkdtemp())
    run.open_window()
    with run.span("solve"):
        jax.block_until_ready(f(f(x)))
    with run.span("wait"):
        time.sleep(0.02)
    jax.block_until_ready(f(x))
    run.close_window()
    src = tr.find_xplane(str(run.trace_dir))
    OUT.parent.mkdir(exist_ok=True)
    shutil.copyfile(src, OUT)
    device, host = tr.load(str(OUT), ["solve", "wait"])
    for plane, events in device.items():
        for ev in sorted(events, key=lambda e: e[1]):
            print(plane, *ev)
    for ev in sorted(host, key=lambda e: e[1]):
        print("host", *ev)
    print(tr.reduce(device, host))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
