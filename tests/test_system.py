"""End-to-end behaviour: the full PageRank pipeline (graph → partitioned
solve → checkpoint → elastic restart) works."""
import numpy as np


def test_pagerank_full_pipeline(tmp_path):
    from repro.core import (
        PartitionedGraph, SolverCheckpoint, l1_norm, pagerank_nosync, pagerank_numpy,
    )
    from repro.graphs import make_dataset

    g = make_dataset("socEpinions1", scale_down=64)
    ref, _ = pagerank_numpy(g, threshold=1e-12)
    pg = PartitionedGraph.from_graph(g, p=4)
    r = pagerank_nosync(pg, threshold=1e-8)
    assert l1_norm(r.pr, ref) < 1e-3
    # checkpoint the solve + elastic restart at a different worker count
    ck = SolverCheckpoint(pr=np.asarray(r.pr), round=int(r.iterations), n=g.n, p=4)
    ck.save(str(tmp_path / "pr"))
    ck2 = SolverCheckpoint.load(str(tmp_path / "pr")).reshard(new_p=8)
    assert ck2.p == 8 and ck2.pr[: g.n].sum() > 0
