"""Per-kernel interpret-mode allclose vs the pure-jnp oracles, with
hypothesis shape/dtype sweeps (per the deliverable-(c) contract)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import pagerank_numpy, l1_norm
from repro.graphs import build_blocked_coo, rmat_graph
from repro.kernels.spmv import PallasGraph, pagerank_pallas, spmv_blocked, spmv_blocked_ref, spmv_ref
from repro.kernels.spmv import kernel


# ---------------------------------------------------------------------------
# SpMV kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block,cap", [(128, 256), (256, 512), (64, 128)])
def test_spmv_kernel_matches_oracle(block, cap, rng):
    g = rmat_graph(9, avg_degree=5, seed=3)
    b = build_blocked_coo(g, block=block, tile_cap=cap)
    contrib = np.zeros(b.n_blocks * block, np.float32)
    contrib[: g.n] = rng.random(g.n).astype(np.float32)
    cb = jnp.asarray(contrib.reshape(b.n_blocks, block))
    out = spmv_blocked(
        cb,
        jnp.asarray(b.tiles_src_local), jnp.asarray(b.tiles_dst_local),
        jnp.asarray(b.tiles_valid), jnp.asarray(b.tile_src_block),
        jnp.asarray(b.tile_dst_block), block=block, interpret=True,
    )
    ref = spmv_blocked_ref(cb, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-6)


def test_blocked_layout_is_edge_permutation(rng):
    g = rmat_graph(8, avg_degree=4, seed=5)
    b = build_blocked_coo(g, block=64, tile_cap=128)
    contrib = rng.random(g.n).astype(np.float32)
    pad = np.zeros(b.n_blocks * 64, np.float32)
    pad[: g.n] = contrib
    ref_plain = spmv_ref(jnp.asarray(contrib), jnp.asarray(g.src), jnp.asarray(g.dst), g.n)
    ref_blocked = spmv_blocked_ref(jnp.asarray(pad.reshape(b.n_blocks, 64)), b)
    np.testing.assert_allclose(
        np.asarray(ref_blocked).reshape(-1)[: g.n], np.asarray(ref_plain), rtol=1e-5
    )


@given(st.integers(6, 9), st.integers(2, 7), st.integers(0, 1000))
@settings(max_examples=10)
def test_property_spmv_kernel_random_graphs(scale, deg, seed):
    g = rmat_graph(scale, avg_degree=deg, seed=seed)
    b = build_blocked_coo(g, block=128, tile_cap=256)
    rng = np.random.default_rng(seed)
    contrib = np.zeros(b.n_blocks * 128, np.float32)
    contrib[: g.n] = rng.random(g.n).astype(np.float32)
    cb = jnp.asarray(contrib.reshape(b.n_blocks, 128))
    out = spmv_blocked(
        cb,
        jnp.asarray(b.tiles_src_local), jnp.asarray(b.tiles_dst_local),
        jnp.asarray(b.tiles_valid), jnp.asarray(b.tile_src_block),
        jnp.asarray(b.tile_dst_block), block=128, interpret=True,
    )
    ref = spmv_blocked_ref(cb, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-6)


def _contraction_errors(r, block, cap, seed=0):
    """One tile's gather and scatter through the kernels' one-hot
    contraction: whether the gather equals ``contrib[:, src]`` bit for bit,
    and the scatter's largest distance from a float64 ``np.add.at``, in f32
    ulps of the reference."""
    rng = np.random.default_rng(seed)

    def values(shape):  # 1e-12 to 1, a tenth exact zeros
        x = (10.0 ** rng.uniform(-12, 0, shape)).astype(np.float32)
        x[rng.random(shape) < 0.1] = 0
        return x

    contrib, y = values((r, block)), values((r, cap))
    src = rng.integers(0, block, (1, cap), dtype=np.int32)
    dst = rng.integers(0, block // 8, (1, cap), dtype=np.int32)  # collisions
    gathered = np.asarray(kernel._contract(
        jnp.asarray(contrib), kernel._onehot(jnp.asarray(src), block),
        kernel._GATHER))
    exact = np.array_equal(gathered.view(np.uint32),
                           contrib[:, src[0]].view(np.uint32))
    scattered = np.asarray(kernel._contract(
        jnp.asarray(y), kernel._onehot(jnp.asarray(dst), block),
        kernel._SCATTER), np.float64)
    ref = np.zeros((r, block))
    np.add.at(ref.T, dst[0], y.T.astype(np.float64))
    ulp = np.spacing(np.abs(ref).astype(np.float32)).astype(np.float64)
    return exact, float(np.max(np.abs(scattered - ref) / ulp))


@pytest.mark.parametrize("r,block,cap,parts", [
    (r, block, cap, 3) for r in (1, 8)
    for block, cap in ((128, 128), (640, 128), (1024, 128))
] + [(8, 1024, 128, 2)])
def test_tile_contraction_keeps_f32(r, block, cap, parts, monkeypatch):
    """The bf16 one-hot against a three-way bf16 split of the f32 operand
    gathers exactly and scatters within 4 f32 ulps of float64; a two-way
    split (16 significant bits) fails both checks."""
    monkeypatch.setattr(kernel, "_PARTS", parts)
    exact, ulps = _contraction_errors(r, block, cap)
    if parts == 3:
        assert exact and ulps <= 4, (exact, ulps)
    else:
        assert not exact and ulps > 4, (exact, ulps)


def test_pallas_pagerank_end_to_end():
    g = rmat_graph(9, avg_degree=6, seed=2)
    pr_ref, _ = pagerank_numpy(g, threshold=1e-12)
    pgk = PallasGraph.build(g, block=128, tile_cap=256)
    r = pagerank_pallas(pgk, threshold=1e-7, interpret=True)
    assert l1_norm(r.pr, pr_ref) < 1e-3
