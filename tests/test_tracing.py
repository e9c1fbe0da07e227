"""The program's tracing: the PPR serving spans and their stats in a real
profiler trace, the kernels' grid-steps table, the per-sweep errors of the
engine step, and the arithmetic of the benchmark's readers of them."""
import contextlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core.solver import build_variant
from repro.graphs import build_blocked_coo, rmat_graph
from repro.serving.ppr_engine import PPREngine, PPRQuery, make_query_stream
from repro.serving.runtime import ServingRuntime
from repro.utils import tracing

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # the benchmark's readers live in bench/
    sys.path.insert(0, str(ROOT))

from bench import program_spans as ps  # noqa: E402
from bench.harness import reader  # noqa: E402
from bench.trace import find_xplane  # noqa: E402

ANSWERS = Path(__file__).with_name("data") / "ppr_engine_answers.json"
BACKENDS = [("jax", {}), ("pallas", dict(block=16, tile_cap=32))]


@pytest.fixture(scope="module")
def g64():
    return rmat_graph(6, avg_degree=6, seed=3)


@contextlib.contextmanager
def traced(directory):
    """Profile the block inside a ``window`` span, as the benchmark does,
    and yield a holder whose ``trace`` is the program spans read back."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out = SimpleNamespace(trace=None)
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        with tracing.span("window"):
            yield out
    finally:
        jax.profiler.stop_trace()
    out.trace = ps.load(find_xplane(str(directory)))


def test_serving_spans_follow_each_query(g64, tmp_path):
    """Every solved query is offered, admitted and harvested once, in that
    order; the steps' ``active`` add up to the engine's busy slot·steps and
    each harvest's ``sweeps`` is the response's ``iterations``."""
    queries = make_query_stream(g64.n, 10, repeat_fraction=0.3, seed=4)
    rt = ServingRuntime(PPREngine(g64, slots=4, threshold=1e-7),
                        queue_depth=4)
    with traced(tmp_path) as out:
        responses = rt.serve(queries)
        # asked again once answered: the result cache answers
        responses += rt.serve([PPRQuery(qid=10 + q.qid, seeds=q.seeds)
                               for q in queries[:2]])
    t = out.trace
    assert sorted(r.qid for r in responses) == list(range(12))
    solved = {r.qid: r for r in responses if not r.cached}
    assert len(solved) == 10
    by_qid = {}
    for s in t.spans:
        if "qid" in s.args:
            by_qid.setdefault(s.args["qid"], []).append(s)
    for qid, r in solved.items():
        names = [s.name for s in by_qid[qid]]
        assert names == ["ppr.offer", "ppr.admit", "ppr.harvest"], names
        offer, admit, harvest = by_qid[qid]
        assert offer.args["outcome"] == "queued"
        assert harvest.args["sweeps"] == r.iterations
        assert bool(harvest.args["warm"]) == bool(admit.args["warm"]) \
            == r.warm_start
        assert admit.args["queue_ms"] >= 0.0
    for r in responses:
        if r.cached:
            assert [(s.name, s.args["outcome"]) for s in by_qid[r.qid]] == [
                ("ppr.offer", "cached")]
    steps = t.named("ppr.step")
    assert sum(s.args["active"] for s in steps) == rt.engine.busy_slot_steps
    assert [s.args["step"] for s in steps] == list(range(len(steps)))
    assert steps[-1].args["active_after"] == 0
    assert max(s.args["queued"] for s in steps) > 0  # queries waited
    # the step's children nest inside it
    for child in ("ppr.dispatch", "ppr.sync"):
        assert len(t.named(child)) == len(steps)
        for st, c in zip(steps, t.named(child)):
            assert st.start <= c.start and c.end <= st.end
    assert sum(s.args["n"] for s in t.named("ppr.cache_insert")) == \
        len(solved)


def test_converged_sweep_bounds_and_warm_repeat(g64, tmp_path):
    """A harvest's ``converged_sweep`` never exceeds its ``sweeps``, and a
    repeat that starts from the cold original's converged vector converges
    no later than the original did."""
    eng = PPREngine(g64, slots=2, threshold=1e-7, iters_per_step=4)
    seeds = [(5,), (9, 17), (), (33,)]
    with traced(tmp_path) as out:
        cold = eng.drain([PPRQuery(qid=i, seeds=s) for i, s in
                          enumerate(seeds)])
        warm = eng.drain([PPRQuery(qid=10 + i, seeds=s) for i, s in
                          enumerate(seeds)])
    assert not any(r.warm_start for r in cold)
    assert all(r.warm_start for r in warm)
    h = {s.args["qid"]: s.args for s in out.trace.named("ppr.harvest")}
    assert len(h) == 2 * len(seeds)
    for a in h.values():
        assert 1 <= a["converged_sweep"] <= a["sweeps"]
        assert a["sweeps"] - a["converged_sweep"] < eng.iters_per_step
    for i in range(len(seeds)):
        assert h[10 + i]["converged_sweep"] <= h[i]["converged_sweep"]


def test_grid_steps_table_after_nosync_solve():
    """A solve records the real tiles of its layout, the pads that make
    whole groups of a grid step not counted, even when the kernel's trace
    is cached."""
    g = rmat_graph(7, avg_degree=5, seed=11)
    v, pg = build_variant("pallas_nosync", g, block=32, tile_cap=64)
    real = build_blocked_coo(g, block=32, tile_cap=64).num_tiles
    assert pg.tiles_src_local.shape[0] > real  # the layout holds pads
    for _ in range(2):
        tracing.GRID_STEPS.pop("spmv_gs_pass", None)
        r = v.run(pg, d=0.85, threshold=1e-6, max_iter=200,
                  handle_dangling=False)
        assert int(r.iterations) > 1
        assert tracing.GRID_STEPS["spmv_gs_pass"] == real


@pytest.mark.parametrize("backend,opts", BACKENDS)
def test_multi_step_returns_every_sweeps_error(g64, backend, opts):
    """``multi_step`` over k sweeps returns each sweep's per-row change:
    row j equals what one-sweep steps return for sweep j, so the last row
    is the change of the last sweep, which the engine harvests on."""
    k = 3
    full = PPREngine(g64, slots=4, iters_per_step=k, backend=backend,
                     **opts)
    one = PPREngine(g64, slots=4, iters_per_step=1, backend=backend, **opts)
    for eng in (full, one):
        for i, s in enumerate([(1,), (2, 40), (), (63,)]):
            assert eng.submit(PPRQuery(qid=i, seeds=s))
    frozen = np.zeros(4, dtype=bool)
    frozen[2] = True  # a frozen row changes by exactly 0
    errs = full._backend.step(frozen)
    assert errs.shape == (k, 4)
    per_sweep = np.concatenate([one._backend.step(frozen) for _ in range(k)])
    np.testing.assert_array_equal(errs, per_sweep)
    assert (errs[:, 2] == 0).all() and errs[0, [0, 1, 3]].all()
    np.testing.assert_array_equal(np.asarray(full._backend.state),
                                  np.asarray(one._backend.state))


@pytest.mark.parametrize("backend,opts", BACKENDS)
def test_harvested_answers_unchanged(g64, backend, opts):
    """Answers, sweep counts and warm starts on a fixed query set equal
    those recorded from the engine when its step returned only the last
    sweep's errors (``data/ppr_engine_answers.json``).  The pallas values
    were recorded again when the kernels' tile contraction became bf16
    one-hots against a three-way bf16 split: they moved by at most 1.4e-7
    relative, the indices, sweep counts and warm starts not at all."""
    expected = json.loads(ANSWERS.read_text())[backend]
    qs = make_query_stream(g64.n, 12, top_k=5, repeat_fraction=0.3, seed=1)
    eng = PPREngine(g64, slots=4, threshold=1e-7, iters_per_step=4,
                    backend=backend, **opts)
    got = {str(r.qid): r for r in eng.drain(qs)}
    assert sorted(got) == sorted(expected)
    for qid, want in expected.items():
        r = got[qid]
        assert list(r.seeds) == want["seeds"]
        assert [int(i) for i in r.indices] == want["indices"]
        assert [float(v) for v in r.values] == want["values"]
        assert (r.iterations, r.warm_start) == (want["iterations"],
                                                want["warm_start"])


# ---------------------------------------------------------------------------
# the benchmark's readers, on hand-built events
# ---------------------------------------------------------------------------

MS = 1_000_000  # ns
CHIP = "/device:TPU:0"


def _span(name, start_ms, end_ms, **args):
    return ps.Span(name, int(start_ms * MS), int(end_ms * MS), args)


def _step(i, start, end, active, active_after, queued, dispatch):
    """A ``ppr.step`` with its ``ppr.dispatch`` [dispatch, dispatch + 1)."""
    return [_span("ppr.step", start, end, step=i, active=active, slots=8,
                  queued=queued, active_after=active_after),
            _span("ppr.dispatch", dispatch, dispatch + 1)]


def _hand_trace(ops=(), modules=(), host=()):
    host = [_span("window", 0, 1000), *host,
            _span("ppr.admit", 1200, 1201, qid=99, queue_ms=1e6)]  # outside
    return ps.window(list(host), {CHIP: list(ops)}, {CHIP: list(modules)})


def test_admit_wait_reader_is_p90_of_queue_ms():
    """Ten admits waiting 1..10 ms: the nearest-rank p90 is 9 ms; the
    admit after the window is left out."""
    t = _hand_trace(host=[_span("ppr.admit", i, i + 0.1, qid=i,
                                queue_ms=float(i + 1)) for i in range(10)])
    assert reader("admit_wait_ms.ppr_steady").value(t) == 9.0


def test_sweeps_and_occupancy_readers():
    """Harvests of 16, 8 and 24 sweeps, converged at 11, 2 and 20, one of
    them warm: mean 16 sweeps, 5 past convergence, a third warm.  Steps
    with 8, 4 and 2 of 8 slots active: 14 of 24 slot·steps, 58.33%."""
    t = _hand_trace(host=[
        _span("ppr.harvest", 10, 11, qid=0, sweeps=16, converged_sweep=11,
              warm=0),
        _span("ppr.harvest", 20, 21, qid=1, sweeps=8, converged_sweep=2,
              warm=1),
        _span("ppr.harvest", 30, 31, qid=2, sweeps=24, converged_sweep=20,
              warm=0),
        _span("ppr.step", 0, 5, step=0, active=8, slots=8),
        _span("ppr.step", 5, 9, step=1, active=4, slots=8),
        _span("ppr.step", 9, 12, step=2, active=2, slots=8)])
    got = reader("sweeps_per_query.ppr_steady").value(t)
    assert got["value"] == pytest.approx(16.0)
    assert got["past_convergence"] == pytest.approx(5.0)
    assert got["warm_share"] == pytest.approx(100.0 / 3)
    assert reader("slot_occupancy.ppr_steady").value(t) == \
        pytest.approx(100.0 * 14 / 24)


def test_step_turnaround_reader_counts_gaps_with_work_left():
    """Four steps whose device programs run [10, 100), [104, 200), [206,
    300), [500, 600) ms, dispatched at 9.5, 103, 205 and 499 ms.  The gap
    after step 0 (4 ms) holds a 1-ms row read at [101, 102), so 3 ms idle;
    after step 1, 6 ms idle; after step 2 nothing was left (active_after 0,
    queued 0), so its 200-ms gap is not counted.  Median of 3 and 6: 4.5 ms.

    On the host the idle pieces fall in: [100, 101) step 0's harvest;
    [102, 103) an admit; [103, 103.5) step 1's dispatch; [103.5, 104) step
    1 itself; [200, 202) step 1's harvest; [202, 205) no span; [205, 206)
    step 2's dispatch.  Over two gaps: harvest 1.5 ms, admit 0.5, dispatch
    0.75, step 0.25, none 1.5, adding up to the mean gap of 4.5."""
    ops = [("multi", 10, 100), ("slice", 101, 102), ("multi", 104, 200),
           ("multi", 206, 300), ("multi", 500, 600)]
    ops = [(n, s * MS, e * MS) for n, s, e in ops]
    mods = [("jit_multi_step(1)", s, e) for n, s, e in ops if n == "multi"]
    mods.append(("jit_row(2)", 101 * MS, 102 * MS))
    host = [
        _span("ppr.step", 9, 102, step=0, active=8, slots=8, queued=3,
              active_after=5),
        _span("ppr.dispatch", 9.5, 10),
        _span("ppr.harvest", 100, 102, qid=0, sweeps=8, converged_sweep=8,
              warm=0),
        _span("ppr.admit", 102, 103, qid=5, queue_ms=1.0),
        _span("ppr.step", 103, 202, step=1, active=8, slots=8, queued=0,
              active_after=2),
        _span("ppr.dispatch", 103, 103.5),
        _span("ppr.harvest", 200, 202, qid=1, sweeps=8, converged_sweep=3,
              warm=0),
        *_step(2, 205, 300, 2, 0, 0, 205),
        *_step(3, 499, 600, 1, 0, 0, 499),
    ]
    t = _hand_trace(ops, mods, host)
    assert [m[1] // MS for _, _, m in ps.steps(t, CHIP)] == [10, 104, 206,
                                                             500]
    got = reader("step_turnaround_ms.ppr_steady").value(t)
    assert got["value"] == pytest.approx(4.5)
    assert got["mean"] == pytest.approx(4.5) and got["gaps"] == 2
    assert got["by_span"] == pytest.approx({
        "ppr.harvest": 1.5, "ppr.admit": 0.5, "ppr.dispatch": 0.75,
        "ppr.step": 0.25, ps.NONE: 1.5})
    assert ps.clock_offsets_ms(t, CHIP) == pytest.approx([0.5, 1, 1, 1])


def test_tile_step_reader_per_grid_step(monkeypatch):
    """3 s of ``spmv_gs_pass`` ops over 30 sweeps of 1,000 grid steps:
    100 µs a grid step; other ops and unrecorded kernels are ignored."""
    op_s = {"spmv_gs_pass.11": 2.0, "spmv_gs_pass.12": 1.0, "while.21": 3.0,
            "spmv_gs_pass_multi.4": 0.5}
    got = reader("tile_step_us.solve").value(
        op_s, 30, {"spmv_gs_pass": 1000, "spmv_gs_pass_multi": 10})
    assert got == {"value": pytest.approx(100.0), "grid_steps": 1000,
                   "kernel": "spmv_gs_pass"}
    assert reader("tile_step_us.ppr_steady").value(op_s, 30, {}) is None
    run = SimpleNamespace(facts={"sweeps": 30, "compiles_in_window": 0})
    trace = SimpleNamespace(op_s={"spmv_gs_pass_multi.4": 0.3})
    monkeypatch.setitem(tracing.GRID_STEPS, "spmv_gs_pass_multi", 100)
    assert reader("tile_step_us.ppr_steady").read(run, trace)["value"] == \
        pytest.approx(100.0)
    assert reader("compiles_in_window.solve").read(run, trace) == 0


def test_readers_report_nothing_without_program_spans():
    """A trace of a program that has no spans of its own."""
    t = _hand_trace(ops=[("multi", 10 * MS, 20 * MS)],
                    modules=[("jit_multi_step(1)", 10 * MS, 20 * MS)])
    t.spans.clear()  # drop the admit outside the window too
    for name in ("admit_wait_ms", "sweeps_per_query", "slot_occupancy",
                 "step_turnaround_ms"):
        assert reader(f"{name}.ppr_steady").value(t) is None
