"""Infra substrates: the solver checkpoint (incl. elastic reshard) and the
dataset registry."""
import os

import numpy as np

from repro.core import SolverCheckpoint
from repro.graphs.datasets import DATASETS, make_dataset


def test_solver_checkpoint_elastic_reshard(tmp_path):
    ck = SolverCheckpoint(pr=np.arange(100, dtype=np.float64), round=5, n=100, p=4)
    path = os.path.join(str(tmp_path), "solver")
    ck.save(path)
    ck2 = SolverCheckpoint.load(path).reshard(new_p=8)
    assert ck2.p == 8
    np.testing.assert_array_equal(ck2.pr[:100], ck.pr)


def test_dataset_registry_mirrors_table1():
    # 4 web + 4 social + 4 road + 7 synthetic + the rmatSkew adaptive fixture
    assert len(DATASETS) == 20
    assert DATASETS["rmatSkew"].family == "skewed"
    g = make_dataset("webStanford", scale_down=512)
    assert g.n >= 64 and g.m >= 128
    g2 = make_dataset("roaditalyosm", scale_down=4096)
    # road networks are near-uniform: max degree far below web graphs
    gw = make_dataset("webBerkStan", scale_down=4096)
    assert g2.out_degree.max() <= gw.out_degree.max() * 2
