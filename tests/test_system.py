"""End-to-end behaviour: a tiny LM actually learns on the synthetic corpus;
the full PageRank pipeline (graph → blocked layout → solver → checkpoint)
works; the dry-run spec builder produces valid abstract cells for a small
mesh in-process."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.tokens import DataConfig, SyntheticCorpus
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step


def test_tiny_lm_learns():
    cfg = dataclasses.replace(
        get_config("stablelm-3b").reduced(), dtype="float32", n_layers=2, vocab=128
    )
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    data = SyntheticCorpus(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8, seed=0))
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=3e-3, warmup_steps=5), moe_dispatch="dense", ce_chunk=32))
    losses = []
    it = data.batches(steps=30)
    for i, tokens in enumerate(it):
        state, metrics = step(state, {"tokens": jnp.asarray(tokens)})
        losses.append(float(metrics["loss"]))
    # learnable bigram structure → loss must drop substantially
    assert np.mean(losses[-5:]) < np.mean(losses[:3]) - 0.3, losses[:3] + losses[-5:]


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


def test_build_cell_in_process_small_mesh():
    """The dry-run builders produce lower()-able cells on whatever devices
    exist (1 here) — the 512-device path is exercised by launch/dryrun.py."""
    from repro.configs import ShapeSpec
    from repro.launch.specs import build_cell
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("qwen2-vl-2b").reduced()
    for kind in ("train", "prefill", "decode"):
        shape = ShapeSpec(kind, 64, 4, kind)
        step, args, in_sh, meta = build_cell(cfg, shape, mesh)
        with mesh:
            lowered = jax.jit(step, in_shardings=in_sh).lower(*args)
        assert lowered is not None
