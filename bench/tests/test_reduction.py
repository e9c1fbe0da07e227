"""The yardstick's arithmetic: required work per sweep, the peaks table,
the trace reduction, and the traffic generator's fixed amounts of work."""
from pathlib import Path

import numpy as np
import pytest

from bench import graph, queries, trace, work

MS = 1_000_000  # ns
RECORDED = Path(__file__).with_name("data") / "tpu_window.xplane.pb"


def test_graph500_18_sweep_work():
    n, m = 2**18, 16 * 2**18
    assert work.sweep_bytes(n, m, 1) == 8 * m + 12 * n == 36_700_160
    assert work.sweep_flops(n, m, 8) == 16 * m
    t, which = work.sweep_bound(n, m, 1, "TPU v5 lite")
    assert which == "memory" and t == pytest.approx(36_700_160 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_reduce_hand_counted():
    """Two chips, spans on the host, a window from 0 to 100 ms.

    chip A: ops [10, 30) and [20, 40) overlap -> busy [10, 40); op [60, 70).
            busy 40 ms; gaps [0, 10) 10 ms, [40, 60) 20 ms, [70, 100) 30 ms.
    chip B: op [0, 100) starts before nothing; busy 100 ms, no gap.
    Mean busy (40 + 100) / 2 = 70 ms.  Ops outside the window are cut."""
    device = {
        "/device:TPU:0": [("fusion", 10 * MS, 30 * MS),
                          ("fusion", 20 * MS, 40 * MS),
                          ("kernel", 60 * MS, 70 * MS),
                          ("kernel", 150 * MS, 160 * MS)],
        "/device:TPU:1": [("copy", -5 * MS, 100 * MS)],
    }
    host = [("window", 0, 100 * MS), ("pump", 0, 12 * MS),
            ("wait", 35 * MS, 65 * MS), ("pump", 65 * MS, 80 * MS)]
    r = trace.reduce(device, host)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.07)
    assert r.idle_share == pytest.approx(0.3)
    assert r.op_s == pytest.approx({"fusion": 0.04, "kernel": 0.01,
                                    "copy": 0.1})
    # [0,10): pump; [40,60): wait; [70,100): pump 10 ms, no span 20 ms
    assert [(n, round(s, 6)) for n, s in r.gaps] == [
        ("no_span", 0.03), ("wait", 0.02), ("pump", 0.01)]
    bd = r.breakdown(top=2)
    assert bd["device_ops"] == [["copy", pytest.approx(0.1)],
                                ["fusion", pytest.approx(0.04)]]
    assert len(bd["idle_gaps"]) == 2


def test_reduce_needs_one_window():
    with pytest.raises(ValueError, match="window"):
        trace.reduce({}, [("pump", 0, 1)])


def test_query_kinds_fixed_per_count():
    mix = {"repeat_fraction": 0.25, "multi_seed_fraction": 0.15,
           "global_fraction": 0.05}
    for seed in (1, 2**31 + 5):
        k = queries.kinds(100, mix, graph.rng(seed))
        assert k[0] != "repeat"
        assert [k.count(x) for x in queries.KINDS] == [55, 25, 15, 5]


def test_arrivals_fixed_gaps_in_window():
    a = queries.arrivals(110, 50.0, graph.rng(3))
    b = queries.arrivals(110, 50.0, graph.rng(4))
    assert a[0] == 0.0 and a[-1] < 50.0 and np.all(np.diff(a) > 0)
    # the same gaps, in another order
    assert not np.allclose(a, b)
    assert np.allclose(np.sort(np.r_[np.diff(a), 50.0 - a[-1]]),
                       np.sort(np.r_[np.diff(b), 50.0 - b[-1]]))


def test_reduce_recorded_tpu_trace():
    """A trace recorded on one TPU v5 lite by ``record_trace.py``: inside a
    ``window`` span, ``f(f(x))`` in a ``solve`` span, a 20 ms ``wait``, then
    ``f(x)`` outside any span (``f`` is one fusion after a copy).

    Counted by hand from the events (ns): the window is [50074989,
    72639939).  The chip's clock runs about 1.1 ms behind the host's in
    this trace, so the two calls made in ``solve`` appear at 49069444 to
    49248922, before the window, and are cut.  The last call's ops,
    copy-start [70619463, 70619476), copy-done [70619478, 70625416) and
    fusion [70625418, 70638766), are busy 13 + 5938 + 13348 = 19299 ns.
    The idle gaps: [50074989, 70619463) 20544474 ns, most under ``wait``;
    two of 2 ns between the ops; [70638766, 72639939) 2001173 ns, of
    which 1054173 ns under ``wait`` and the rest in no span."""
    device, host = trace.load(str(RECORDED), ["solve", "wait"])
    assert list(device) == ["/device:TPU:0"]
    assert [e[0] for e in sorted(device["/device:TPU:0"], key=lambda e: e[1])
            ] == ["copy-start", "copy-done", "fusion"] * 3
    assert sorted(host) == [("solve", 50084309, 51039379),
                            ("wait", 51054079, 71692939),
                            ("window", 50074989, 72639939)]
    r = trace.reduce(device, host)
    assert r.window_s == pytest.approx(22_564_950e-9)
    assert r.busy_s == pytest.approx(19_299e-9)
    assert r.op_s == pytest.approx({"copy-start": 13e-9, "copy-done": 5938e-9,
                                    "fusion": 13348e-9})
    assert r.gaps == [("wait", pytest.approx(20_544_474e-9)),
                      ("wait", pytest.approx(2_001_173e-9)),
                      ("wait", pytest.approx(2e-9)),
                      ("wait", pytest.approx(2e-9))]


def test_op_name_from_hlo_text():
    assert trace.op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %x)") == \
        "fusion.3"
    assert trace.op_name("copy-done") == "copy-done"
