"""The harness past its look for a chip, driven at a small size with the
timed path broken underneath: each fault a cell can have must come out as
``correct`` false, and the unbroken run as true."""
import numpy as np
import pytest

from bench import serving
from bench.harness import run_cell

SECONDS = 1.5
SEED = 2**31 + 11  # the driver's seeds are larger than 32 signed bits


def drive(spec):
    return run_cell(spec, SEED, SECONDS, False, "TPU v5 lite")[1]


@pytest.fixture(autouse=True)
def fresh_traces():
    """A fault is planted in a function that jit traces: forget the traces
    of earlier tests so that the planted one is traced, and forget it
    after."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", ["graph500-18.nosync-cold",
                                  "graph500-18.barrier-cold"])
def test_global_sound_run_is_correct(small_spec, cell):
    out = drive(small_spec(cell))
    assert out.correct and out.failed == 0 and out.attempted >= 1
    assert set(out.end_to_end) == {"solve_s"}


def test_global_state_unchanged_is_refused(small_spec, monkeypatch):
    """A sweep that hands its state back unchanged: the solve 'converges'
    at once on the uniform start."""
    import repro.kernels.spmv.ops as ops

    monkeypatch.setattr(ops, "spmv_gs_pass", lambda pr, *a, **k: pr)
    out = drive(small_spec("graph500-18.nosync-cold"))
    assert not out.correct
    assert dict((n, v) for n, v, _ in out.checks)["l1_max"] > 1e-3


@pytest.mark.parametrize("qps", [8.0, 24.0])
def test_ppr_sound_run_is_correct(small_spec, qps):
    out = drive(small_spec("graph500-16.ppr-steady", qps=qps))
    assert out.correct and out.failed == 0 and out.attempted >= 8


def test_ppr_answer_altered_is_refused(small_spec, monkeypatch):
    """A top-k answer altered where the engine produces it."""
    import repro.serving.ppr_engine as eng

    real = eng.topk

    def altered(row, k):
        idx, vals = real(row, k)
        return idx, vals * 1.01

    monkeypatch.setattr(eng, "topk", altered)
    out = drive(small_spec("graph500-16.ppr-steady", qps=8.0))
    assert not out.correct and out.failed > 0


def test_ppr_half_the_batch_left_out_is_refused(small_spec, monkeypatch):
    """A step that advances only the first half of the slots: the rows of
    the other half stand still, so their queries are answered from the
    teleport row (a change of 0 reads as converged) or never."""
    import repro.serving.ppr_engine as eng

    real = eng.PPREngine.step

    def half_step(self):
        keep = self._frozen.copy()
        self._frozen[self.slots // 2:] = True
        try:
            return real(self)
        finally:
            self._frozen[self.slots // 2:] = keep[self.slots // 2:]

    monkeypatch.setattr(eng.PPREngine, "step", half_step)
    monkeypatch.setattr(serving, "DRAIN_S", 1.0)
    # offered well above what the CPU serves, so that every slot fills
    out = drive(small_spec("graph500-16.ppr-steady", qps=40.0))
    assert not out.correct and out.failed > 0


def test_no_chip_exits_without_a_result(capsys):
    from bench import run

    assert run.main(["--workload", "graph500-18.nosync-cold", "--seed", "1",
                     "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""


def test_same_seed_same_inputs(small_spec):
    from bench import graph, queries

    cfg = small_spec("graph500-18.nosync-cold")["config"]["graph"]
    a, b = graph.edges(cfg, SEED), graph.edges(cfg, SEED)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    mix = small_spec("graph500-16.ppr-steady")["mix"]
    assert (queries.seed_sets(500, 50, mix, graph.rng(SEED))
            == queries.seed_sets(500, 50, mix, graph.rng(SEED)))
