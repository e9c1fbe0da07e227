"""Pallas TPU kernel: 2-D blocked SpMV for the PageRank sweep.

TPU adaptation of propagation blocking (paper ref [17], DESIGN.md §5):
edges are pre-bucketed into (dst_block, src_block) tiles so one tile only
touches a single ``block``-sized slice of the contribution vector and a single
``block``-sized output accumulator — both VMEM-resident.

On a CPU the binning/accumulate phases fight DRAM; on TPU the analogous
enemy is HBM→VMEM traffic *and* the lack of fast random gather/scatter.
We remove gather/scatter entirely: within a tile, gather and scatter are both
expressed as **one-hot matmuls on the MXU**, with the one-hot laid out
``(block, cap)`` so the tile's index row broadcasts along sublanes::

    gathered(r, cap) = contrib(r, block) @ onehot(src_local)(block, cap)
    acc(r, block)   += (w·gathered)(r, cap) @ onehot(dst_local)(block, cap)ᵀ

(``r`` = 1 for the global kernels, the batch for the PPR kernel).

The one-hots are bf16 (0 and 1 are exact) and each f32 operand enters as
three bf16 parts stacked on the row axis, so each contraction is one bf16 ×
bf16 matmul with f32 accumulation and f32's 24-bit precision
(:func:`_contract`).  At ``Precision.HIGHEST`` on f32 one-hots the v5e
latched every one-hot register into the MXU six times: 1,536 latches a
step at block 1024 / cap 128, now 128 (docs/KERNELS.md).

Per tile the kernel reads ~3·cap·4 B of edge indices from HBM against
4·cap·block MXU FLOPs per batch row.  On a v5e at block 1024 / cap 128 a
grid step costs ~0.39 µs (~0.94 µs with the six latches), far above either
the HBM or the MXU time of its tile, so the kernel is bound by its
per-step cost, not by HBM (``scripts/tile_step_cost.py``).

Layout rules the TPU compiler imposes (and interpret mode does not): every
block's last two dims must be (8, 128)-aligned or span the whole array, so
per-tile and per-block rows are streamed from ``(T, 1, cap)`` /
``(n_blocks, 1, block)`` views with the leading dim squeezed (``None``), and
whole-pass state is one full ``(n_blocks, block)`` block addressed by row
(``ref[pl.ds(i, 1), :]``).  Scalars ride in SMEM.

Grid: one step per tile, tiles sorted by dst_block → each output block is
resident in VMEM for one contiguous run of grid steps (standard Pallas
reduction/revisiting pattern, initialized via ``pl.when`` on run start).
The Gauss–Seidel wrappers record their grid size in
:data:`repro.utils.tracing.GRID_STEPS` when they trace.
Scalar-prefetched tile→block maps drive the BlockSpec index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.platform import pallas_interpret
from repro.utils.tracing import GRID_STEPS

# one-hot entries are exact in bf16 but the ranks are not: each f32 operand
# of a tile contraction rides as this many bf16 pieces (8 bits each)
_PARTS = 3
# v5e's default scoped VMEM limit, and the chip's physical VMEM per core
_DEFAULT_SCOPED_VMEM = 16 * 2**20
VMEM_CAPACITY = 128 * 2**20
# the most tiles one call takes: the two int32 tile->block maps are
# scalar-prefetched into 1 MiB of SMEM.  The v5e compiler accepts 130,048
# tiles and refuses 130,049 (tests/test_tpu_compile.py)
MAX_TILES = 130_048


def _onehot(local_ids, block: int):
    """``(1, cap)`` int32 row of block-local ids → ``(block, cap)`` bf16
    one-hot (0 and 1 are exact); the row broadcasts along sublanes, so no
    transpose is needed."""
    ids = jax.lax.broadcasted_iota(jnp.int32, (block, local_ids.shape[-1]), 0)
    return jnp.where(ids == local_ids, 1.0, 0.0).astype(jnp.bfloat16)


def _split(x):
    """``(r, n)`` f32 → ``(_PARTS·r, n)`` bf16, stacked on the row axis:
    each part is the bf16 rounding of what the parts above it left, so three
    give ``x = hi + mid + lo`` exactly (three 8-bit significands carry f32's
    24).  Two carry 16 bits, ~8e-6 relative: a lower precision than f32."""
    pieces, rest = [], x
    for _ in range(_PARTS):
        piece = rest.astype(jnp.bfloat16)
        pieces.append(piece)
        rest = rest - piece.astype(jnp.float32)
    return jnp.concatenate(pieces, axis=0)


# (r, block) @ onehot(block, cap), and (r, cap) @ onehot(block, cap)ᵀ
_GATHER = (((1,), (0,)), ((), ()))
_SCATTER = (((1,), (1,)), ((), ()))


def _contract(x, onehot, dims):
    """``x`` (r, ·) f32 against a bf16 one-hot at f32 precision: one bf16 ×
    bf16 MXU matmul of the stacked parts of ``x`` (:func:`_split`) with f32
    accumulation, so each one-hot vreg is latched once (at
    ``Precision.HIGHEST`` on f32 operands the v5e latches it six times),
    then the parts' row groups summed in f32, largest first.  Every product
    with a 0/1 entry is exact: a gather (one nonzero per output) returns
    ``x``'s values bit for bit, a scatter sums them as f32 does."""
    y = jax.lax.dot_general(_split(x), onehot, dims,
                            preferred_element_type=jnp.float32)
    r = x.shape[0]
    out = y[:r]
    for k in range(1, _PARTS):
        out = out + y[k * r:(k + 1) * r]
    return out


def _tile_gather_scatter(src, dst, w, contrib):
    """One tile's gather→scale→scatter as two one-hot MXU matmuls
    (:func:`_contract`); every kernel shares this so their tile math stays
    identical.

    src/dst: (1, cap) int32 local ids; w: (1, cap) f32 validity·weight;
    contrib: (r, block) — returns the (r, block) partial accumulator."""
    block = contrib.shape[-1]
    gathered = _contract(contrib.astype(jnp.float32), _onehot(src, block),
                         _GATHER)  # (r, cap)
    return _contract(gathered * w, _onehot(dst, block), _SCATTER)  # (r, block)


def _rows(x):
    """``(T, cap)`` → ``(T, 1, cap)``: one aligned-or-full block per row."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def _tile_spec(cap: int):
    return pl.BlockSpec((None, 1, cap), lambda t, sb, db: (t, 0, 0))


def _resident_spec(shape):
    """Whole-array block under a constant index map, single-buffered: it is
    fetched (or written back) once, so a second buffer would only cost VMEM."""
    zeros = (0,) * len(shape)
    return pl.BlockSpec(shape, lambda t, sb, db: zeros,
                        pipeline_mode=pl.Buffered(1))


def _vmem_need(resident_bytes: int, step_bytes: int) -> int:
    """The scoped VMEM a kernel asks for: the single-buffered resident
    state, two buffers of every per-step block, and room for the tile
    body's temporaries (:func:`_tile_body_bytes`)."""
    return resident_bytes + 2 * step_bytes + (4 << 20)


def multi_pass_vmem_bytes(n_blocks: int, block: int, cap: int, b: int) -> int:
    """The scoped VMEM :func:`spmv_gs_pass_multi` asks for at a layout: the
    rank, base and state panels (``b`` padded to 8 sublanes) and the two
    per-vertex rows, each single-buffered, plus the tile body."""
    panel_bytes = n_blocks * -(-b // 8) * 8 * block * 4
    resident = 3 * panel_bytes + 2 * n_blocks * block * 4
    return _vmem_need(resident, _tile_body_bytes(block, cap, b))


def _compiler_params(need: int):
    """Sequential grid (the Gauss–Seidel order and the output runs depend on
    it) and a scoped-VMEM limit of ``need`` bytes (:func:`_vmem_need`)."""
    if need > VMEM_CAPACITY:
        raise ValueError(
            f"the kernel needs ~{need / 2**20:.0f} MiB of VMEM, more than a "
            f"TPU core has ({VMEM_CAPACITY >> 20} MiB); shard the vertex "
            f"space first (repro.core.distributed)")
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=max(_DEFAULT_SCOPED_VMEM, need))


def _tile_body_bytes(block: int, cap: int, r: int) -> int:
    """Per-step VMEM of the tile body: the four streamed ``(1, cap)`` f32
    rows (each padded to 8 sublanes), the two bf16 one-hots, each
    contraction's ``_PARTS·r`` stacked rows in bf16 (padded to 16 sublanes)
    and their f32 products (padded to 8), and the ``r``-row f32 panels."""
    stacked = _PARTS * r
    return (4 * 4 * 8 * cap + 2 * 2 * block * cap
            + (2 * -(-stacked // 16) * 16 + 4 * -(-stacked // 8) * 8
               + 4 * max(r, 8)) * (cap + block))


def _spmv_kernel(sb_ref, db_ref, contrib_ref, src_ref, dst_ref, val_ref, out_ref):
    t = pl.program_id(0)
    prev = jnp.maximum(t - 1, 0)
    is_first = (t == 0) | (db_ref[t] != db_ref[prev])

    @pl.when(is_first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = _tile_gather_scatter(src_ref[...], dst_ref[...], val_ref[...],
                               contrib_ref[...])
    out_ref[...] += acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def spmv_blocked(
    contrib_blocks: jax.Array,  # (n_blocks, block) f32 — pr*inv_out, padded
    tiles_src_local: jax.Array,  # (T, cap) int32
    tiles_dst_local: jax.Array,  # (T, cap) int32
    tiles_valid: jax.Array,  # (T, cap) f32
    tile_src_block: jax.Array,  # (T,) int32
    tile_dst_block: jax.Array,  # (T,) int32
    *,
    block: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns acc_blocks (n_blocks, block): sum of contributions per dst."""
    n_blocks = contrib_blocks.shape[0]
    T, cap = tiles_src_local.shape
    row = pl.BlockSpec((None, 1, block), lambda t, sb, db: (sb[t], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[row, _tile_spec(cap), _tile_spec(cap), _tile_spec(cap)],
        out_specs=pl.BlockSpec((None, 1, block), lambda t, sb, db: (db[t], 0, 0)),
    )
    out = pl.pallas_call(
        _spmv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, 1, block), contrib_blocks.dtype),
        compiler_params=_compiler_params(_vmem_need(
            0, 2 * 4 * 8 * block + _tile_body_bytes(block, cap, 1))),
        interpret=pallas_interpret(interpret),
    )(tile_src_block, tile_dst_block, contrib_blocks.reshape(n_blocks, 1, block),
      _rows(tiles_src_local), _rows(tiles_dst_local), _rows(tiles_valid))
    return out.reshape(n_blocks, block)


# ---------------------------------------------------------------------------
# No-Sync (blocked Gauss–Seidel) sweep
# ---------------------------------------------------------------------------
#
# The paper's Alg-3 schedule applied to the blocked kernel: dst blocks are
# swept **in order within one pass**, and every tile reads the *freshest*
# contribution blocks — src blocks below the current dst block have already
# been updated this pass, those at/above still hold the previous pass.  On
# TPU the sequential grid makes this one deterministic member of the paper's
# admissible asynchronous executions (Lemma 2: same fixed point), and Fig-7's
# iteration advantage carries over because fresh reads shorten the spectral
# tail exactly as in the pthread version.
#
# Implementation: the rank state lives in the *output* ref (constant index
# map → one VMEM-resident buffer across the whole grid, written back once at
# the end).  Step 0 copies the input ranks in; each dst-block run accumulates
# its tiles' one-hot-matmul partial sums into a VMEM scratch, then commits
# ``new_j = (base·bias_j + dmass + d·acc_j)·vmask_j`` into the state, so later
# runs gather from it.  The three scalars [base, d, dmass] arrive in SMEM
# (dangling mass kept separate from the base: redistribution is uniform,
# never bias-scaled); per-edge weights stream per tile and the bias is one
# more block-layout VMEM operand — see docs/KERNELS.md for the operand table
# and the resulting ~24 B/vertex VMEM budget (whole-state residency is the
# right trade while the state fits a core's VMEM; beyond that the nosync
# schedule shards first, see core/distributed.py).


def _spmv_gs_kernel(sb_ref, db_ref, params_ref, pr0_ref, inv_ref, vmask_ref,
                    bias_ref, frozen_ref, src_ref, dst_ref, val_ref, wt_ref,
                    pr_ref, acc_ref):
    t = pl.program_id(0)
    num_t = pl.num_programs(0)
    db = db_ref[t]
    sb = sb_ref[t]
    prev = jnp.maximum(t - 1, 0)
    nxt = jnp.minimum(t + 1, num_t - 1)
    is_run_start = (t == 0) | (db_ref[prev] != db)
    is_run_end = (t == num_t - 1) | (db_ref[nxt] != db)

    @pl.when(t == 0)
    def _load_state():
        pr_ref[...] = pr0_ref[...]

    @pl.when(is_run_start)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Fresh gather: contributions come from the current state, not a snapshot.
    # The per-edge weights operand scales each lane of the one-hot contraction
    # (val·wt folds validity and weight; the unweighted caller passes the
    # {0,1} validity mask for wt, making the product a no-op).
    src_rows = pl.ds(sb, 1)
    contrib = pr_ref[src_rows, :] * inv_ref[src_rows, :]
    acc_ref[...] += _tile_gather_scatter(src_ref[...], dst_ref[...],
                                         val_ref[...] * wt_ref[...], contrib)

    @pl.when(is_run_end)
    def _commit_block():
        base = params_ref[0]
        d = params_ref[1]
        dmass = params_ref[2]
        rows = pl.ds(db, 1)
        # per-vertex teleport bias: multiplies the base term only (dangling
        # mass stays uniform); the unbiased caller passes vmask, whose 1s at
        # real vertices reproduce the scalar base exactly.
        new = (base * bias_ref[rows, :] + dmass + d * acc_ref[...]) \
            * vmask_ref[rows, :]
        # perforation (Alg 5): frozen vertices keep their current rank, so
        # in-pass fresh reads by later dst blocks observe the frozen value.
        # The freeze mask is decided OUTSIDE the kernel (the engine's
        # perforation transform); here it is only respected.
        fz = frozen_ref[rows, :]
        pr_ref[rows, :] = (fz * pr_ref[rows, :] + (1.0 - fz) * new
                           ).astype(pr_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def spmv_gs_pass(
    pr_blocks: jax.Array,  # (n_blocks, block) f32 — current ranks, padded
    inv_out_blocks: jax.Array,  # (n_blocks, block) f32 — 1/outdeg, padded
    vmask_blocks: jax.Array,  # (n_blocks, block) f32 — 1 for real vertices
    bias_blocks: jax.Array,  # (n_blocks, block) f32 — teleport-bias multiplier
    frozen_blocks: jax.Array,  # (n_blocks, block) f32 — 1 for perforation-frozen
    params: jax.Array,  # (1, 3) f32 — [base, d, dmass]
    tiles_src_local: jax.Array,  # (T, cap) int32
    tiles_dst_local: jax.Array,  # (T, cap) int32
    tiles_valid: jax.Array,  # (T, cap) f32
    tiles_weight: jax.Array,  # (T, cap) f32 — per-edge weights (0 = padding)
    tile_src_block: jax.Array,  # (T,) int32 — tiles sorted by dst_block
    tile_dst_block: jax.Array,  # (T,) int32 — non-decreasing
    *,
    block: int,
    interpret: bool | None = None,
) -> jax.Array:
    """One full blocked Gauss–Seidel pass; returns the updated rank blocks.

    ``frozen_blocks`` is the VMEM-resident Alg-5 freeze mask: a frozen
    vertex's rank is held at its current value when its dst block commits
    (pass all-zeros for the unperforated schedule — the mask costs one
    VMEM-resident ``(n_blocks, block)`` operand, same footprint as
    ``vmask_blocks``).

    ``tiles_weight`` is the per-edge weights operand (tile layout, one
    ``(1, cap)`` row streamed per grid step alongside the index tiles); it
    scales each edge's gathered contribution inside the one-hot tile matmul.
    ``bias_blocks`` is the per-vertex teleport-bias operand multiplying the
    ``base`` scalar at commit; ``params`` carries ``[base, d, dmass]`` with
    the dangling mass kept separate because redistribution is uniform, never
    bias-scaled.  Unweighted callers pass ``tiles_valid`` / ``vmask_blocks``
    for the two (aliasing the buffers already resident — no extra HBM
    traffic, and ``val·val = val`` for a {0,1} mask)."""
    n_blocks = pr_blocks.shape[0]
    T, cap = tiles_src_local.shape
    GRID_STEPS["spmv_gs_pass"] = T
    state = _resident_spec((n_blocks, block))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [state] * 5 + [_tile_spec(cap)] * 4,
        out_specs=state,
        scratch_shapes=[pltpu.VMEM((1, block), jnp.float32)],
    )
    # five resident inputs + the state, each single-buffered
    resident = 6 * n_blocks * block * 4
    return pl.pallas_call(
        _spmv_gs_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, block), pr_blocks.dtype),
        compiler_params=_compiler_params(
            _vmem_need(resident, _tile_body_bytes(block, cap, 1))),
        interpret=pallas_interpret(interpret),
    )(tile_src_block, tile_dst_block, params.reshape(-1), pr_blocks,
      inv_out_blocks, vmask_blocks, bias_blocks, frozen_blocks,
      _rows(tiles_src_local), _rows(tiles_dst_local), _rows(tiles_valid),
      _rows(tiles_weight))


# ---------------------------------------------------------------------------
# Multi-vector (batched PPR) Gauss–Seidel sweep
# ---------------------------------------------------------------------------
#
# The PPR subsystem solves b personalized rank vectors against ONE graph; the
# tile structure (and thus the HBM edge traffic) is identical for every row,
# so the batched pass amortizes the index streams across the whole batch: the
# same one-hot tile matmuls now contract a (b, block) panel instead of a
# (1, block) row — still MXU work, b× the useful FLOPs per byte of edge data.
#
# Layout: the rank state is (n_blocks, b, block) — block-major so each dst
# block's (b, block) panel is one contiguous VMEM slice, batch on the sublane
# axis (compiled TPU wants b a multiple of 8; interpret mode doesn't care).
# As in spmv_gs_pass the state lives in the output ref under a constant index
# map and is revisited across the whole grid: step 0 copies the input ranks
# in, each dst-block run accumulates tile panels into a (b, block) VMEM
# scratch, and the commit applies the per-row PPR update
#
#     new[row] = (base[row] + d·acc[row]) · vmask
#
# where base = teleport_blocks·((1-d) + d·dangling_mass[row]) is precomputed
# per pass (the per-row teleport matrix generalizes the scalar (1-d)/n of the
# global kernel).  ``frozen_rows`` is the batched form of the freeze mask:
# whole rows (converged serving slots) hold their ranks through the pass —
# per-slot early exit for the continuous-batching PPR engine.  It enters the
# kernel as a (b, 1) column so it broadcasts along lanes.


def _spmv_gs_multi_kernel(sb_ref, db_ref, params_ref, pr0_ref, inv_ref,
                          vmask_ref, frozen_ref, base_ref, src_ref, dst_ref,
                          val_ref, wt_ref, pr_ref, acc_ref):
    t = pl.program_id(0)
    num_t = pl.num_programs(0)
    db = db_ref[t]
    sb = sb_ref[t]
    prev = jnp.maximum(t - 1, 0)
    nxt = jnp.minimum(t + 1, num_t - 1)
    is_run_start = (t == 0) | (db_ref[prev] != db)
    is_run_end = (t == num_t - 1) | (db_ref[nxt] != db)

    @pl.when(t == 0)
    def _load_state():
        pr_ref[...] = pr0_ref[...]

    @pl.when(is_run_start)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Fresh gather of the whole batch panel: (b, block) ranks of src block sb.
    # validity·weight folds the per-edge weights operand into the panel
    # (unweighted callers pass tiles_valid as wt: val² = val for a {0,1} mask)
    contrib = pr_ref[sb] * inv_ref[pl.ds(sb, 1), :]  # (b, block)
    acc_ref[...] += _tile_gather_scatter(src_ref[...], dst_ref[...],
                                         val_ref[...] * wt_ref[...], contrib)

    @pl.when(is_run_end)
    def _commit_block():
        d = params_ref[0]
        fz = frozen_ref[...]  # (b, 1) — 1 for rows held through the pass
        new = (base_ref[db] + d * acc_ref[...]) * vmask_ref[pl.ds(db, 1), :]
        pr_ref[db] = (fz * pr_ref[db] + (1.0 - fz) * new).astype(pr_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def spmv_gs_pass_multi(
    pr_blocks: jax.Array,  # (n_blocks, b, block) f32 — current rank rows
    inv_out_blocks: jax.Array,  # (n_blocks, block) f32 — 1/outdeg, padded
    vmask_blocks: jax.Array,  # (n_blocks, block) f32 — 1 for real vertices
    frozen_rows: jax.Array,  # (1, b) f32 — 1 for rows held through the pass
    base_blocks: jax.Array,  # (n_blocks, b, block) f32 — per-row teleport base
    params: jax.Array,  # (1, 1) f32 — [d]
    tiles_src_local: jax.Array,  # (T, cap) int32
    tiles_dst_local: jax.Array,  # (T, cap) int32
    tiles_valid: jax.Array,  # (T, cap) f32
    tiles_weight: jax.Array,  # (T, cap) f32 — per-edge weights (0 = padding)
    tile_src_block: jax.Array,  # (T,) int32 — tiles sorted by dst_block
    tile_dst_block: jax.Array,  # (T,) int32 — non-decreasing
    *,
    block: int,
    interpret: bool | None = None,
) -> jax.Array:
    """One blocked Gauss–Seidel pass over ``b`` rank rows; returns the
    updated ``(n_blocks, b, block)`` state.

    ``base_blocks`` is the per-row additive term in the same layout as the
    rank state — ``teleport·((1-d) + d·dangling_mass_row)`` for PPR, which
    reduces to the global kernel's scalar base when every row's teleport is
    uniform (per-vertex bias also folds in here: the caller scales the
    teleport rows, so this kernel needs no separate bias operand).
    ``tiles_weight`` is the per-edge weights operand shared across the
    whole batch — one ``(1, cap)`` stream per tile scales the ``(b, cap)``
    gathered panel; unweighted callers pass ``tiles_valid``.  ``frozen_rows``
    freezes whole rows (serving slots), not single vertices; with ``b=1``,
    all-zeros mask and a uniform base this pass is exactly
    :func:`spmv_gs_pass` on one vector."""
    n_blocks, b, _ = pr_blocks.shape
    T, cap = tiles_src_local.shape
    GRID_STEPS["spmv_gs_pass_multi"] = T
    vertex = _resident_spec((n_blocks, block))
    panel = _resident_spec((n_blocks, b, block))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), panel, vertex,
                  vertex, _resident_spec((b, 1)), panel]
        + [_tile_spec(cap)] * 4,
        out_specs=panel,
        scratch_shapes=[pltpu.VMEM((b, block), jnp.float32)],
    )
    return pl.pallas_call(
        _spmv_gs_multi_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, b, block), pr_blocks.dtype),
        compiler_params=_compiler_params(
            multi_pass_vmem_bytes(n_blocks, block, cap, b)),
        interpret=pallas_interpret(interpret),
    )(tile_src_block, tile_dst_block, params.reshape(-1), pr_blocks,
      inv_out_blocks, vmask_blocks, frozen_rows.reshape(b, 1), base_blocks,
      _rows(tiles_src_local), _rows(tiles_dst_local), _rows(tiles_valid),
      _rows(tiles_weight))
