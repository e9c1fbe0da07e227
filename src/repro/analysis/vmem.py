"""Static VMEM/BlockSpec analyzer for the Pallas SpMV kernel family.

The kernels in ``repro.kernels.spmv.kernel`` keep their whole rank state
VMEM-resident (constant index maps revisited across the grid), which is a
*budget*, not a convention: VMEM is ~16 MB/core, and docs/KERNELS.md used to
hand-tabulate the resulting ~24 B/vertex figure.  This pass computes it from
the program instead:

1. **Capture** — each kernel wrapper is called with symbolic
   ``jax.ShapeDtypeStruct`` arguments whose dimensions are distinct sentinel
   primes, with ``pl.pallas_call`` monkeypatched to record the grid spec
   instead of executing.  Nothing runs; the captured ``grid``, ``in_specs``,
   ``out_specs`` and ``scratch_shapes`` ARE the kernel's memory contract.
2. **Symbolize** — every dimension is attributed to one of the symbols
   ``(n_blocks, block, b, cap, T, groups)`` by its sentinel value, so footprints
   come out as closed forms, not numbers for one shape.
3. **Classify residency** — an operand whose index map is constant across
   the whole grid (for any prefetch content) is VMEM-resident for the whole
   pass; one whose map varies is streamed (double-buffered: 2 blocks live).
4. **Check** — index-map ranges are evaluated over the grid with extreme
   prefetch values and must stay inside each operand's block grid, the
   per-vertex budget is computed (resident operands scaling with
   ``n_blocks``), and the max vertices/core before VMEM overflows becomes a
   computed number that docs/KERNELS.md embeds verbatim
   (``scripts/docs_check.py`` diffs the generated table).

The capture helper is public (:func:`capture_grid_spec`) so tests can feed
deliberately-broken kernels — an over-budget operand set, an out-of-range
index map — through the same analyzer that certifies the real family.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis.findings import Finding

# v5e's default scoped VMEM per kernel (docs/KERNELS.md quotes the same
# figure); the kernels may raise it, so the analyzer's budget is the size a
# kernel fits without doing so.
VMEM_BYTES = 16 * 2**20

# Sentinel primes: each symbol gets a distinct value no other dimension can
# collide with (the real kernels also use dims 1, 3 and 8, which stay
# literal).  ``T`` is the prime 13 times the 8 tiles of a Gauss–Seidel
# grid step, so ``groups`` (T / 8, one grid step each) is 13.
SYMBOLS: dict[str, int] = {
    "n_blocks": 5, "block": 7, "cap": 11, "T": 104, "groups": 13, "b": 17,
}
_VALUE_TO_SYMBOL = {v: k for k, v in SYMBOLS.items()}


def _symbolize(shape: Sequence[int]) -> tuple:
    """Map a sentinel-valued shape to its symbolic form, e.g. (5, 7) ->
    ("n_blocks", "block"); dims that match no sentinel stay literal ints."""
    return tuple(None if d is None else _VALUE_TO_SYMBOL.get(int(d), int(d))
                 for d in shape)


def _eval_dim(dim, env: dict) -> int:
    if dim is None:  # squeezed block dim: one element
        return 1
    return int(env[dim]) if isinstance(dim, str) else int(dim)


def _nbytes(shape: Sequence, itemsize: int, env: dict) -> int:
    n = itemsize
    for d in shape:
        n *= _eval_dim(d, env)
    return n


@dataclasses.dataclass
class Operand:
    """One pallas_call operand's symbolic memory contract."""

    name: str
    kind: str  # "prefetch" | "smem" | "input" | "output" | "scratch"
    shape: tuple  # symbolic full shape
    block_shape: tuple | None  # symbolic BlockSpec shape (None: no BlockSpec)
    dtype: str
    itemsize: int
    resident: bool  # constant index map -> whole-pass VMEM residency

    def block_bytes(self, env: dict) -> int:
        shape = self.block_shape if self.block_shape is not None else self.shape
        return _nbytes(shape, self.itemsize, env)

    def scales_with_vertices(self) -> bool:
        """True when the operand's resident footprint grows with the padded
        vertex count (its block shape spans the (n_blocks, block) plane)."""
        bs = self.block_shape or ()
        return self.resident and "n_blocks" in bs and "block" in bs

    def per_vertex_coeffs(self) -> tuple[float, float]:
        """Bytes per padded vertex as ``const + coeff_b * b`` — the batch
        symbol is kept symbolic so the multi-vector kernel's budget reads as
        a formula, not a number for one b."""
        if not self.scales_with_vertices():
            return (0.0, 0.0)
        rest = [d for d in self.block_shape if d not in ("n_blocks", "block")]
        const, b_coeff = float(self.itemsize), 0.0
        for d in rest:
            if d == "b":  # batch dim appears at most once per operand
                const, b_coeff = 0.0, const
            else:
                const *= _eval_dim(d, {})
                b_coeff *= _eval_dim(d, {})
        return (const, b_coeff)


@dataclasses.dataclass
class KernelReport:
    """The analyzer's verdict on one kernel: symbolic operand table, budget
    coefficients, and any contract findings."""

    kernel: str
    grid: tuple  # symbolic grid, e.g. ("T",)
    operands: list[Operand]
    findings: list[Finding] = dataclasses.field(default_factory=list)

    # ---- budget algebra --------------------------------------------------

    def per_vertex_bytes(self, b: int = 1) -> float:
        """Resident bytes per padded vertex (the docs' "B/vertex" figure)."""
        const = sum(o.per_vertex_coeffs()[0] for o in self.operands)
        bcoef = sum(o.per_vertex_coeffs()[1] for o in self.operands)
        return const + bcoef * b

    def per_vertex_expr(self) -> str:
        """Human form of :meth:`per_vertex_bytes`, e.g. ``"24"`` or
        ``"8 + 12·b"`` — embedded in the generated docs table."""
        const = sum(o.per_vertex_coeffs()[0] for o in self.operands)
        bcoef = sum(o.per_vertex_coeffs()[1] for o in self.operands)
        if bcoef == 0:
            return f"{const:g}"
        return f"{const:g} + {bcoef:g}·b"

    def fixed_bytes(self, *, block: int, cap: int, b: int = 1) -> int:
        """VMEM bytes that do NOT scale with the vertex count: streamed
        operands (double-buffered — two blocks in flight), scratch buffers,
        and small resident operands (params, row masks)."""
        env = dict(SYMBOLS)
        env.update(block=block, cap=cap, b=b)
        total = 0
        for o in self.operands:
            if o.kind in ("prefetch", "smem"):
                continue  # scalar prefetch and SMEM operands are not VMEM
            if o.kind == "scratch":
                total += o.block_bytes(env)
            elif o.resident and not o.scales_with_vertices():
                total += o.block_bytes(env)
            elif not o.resident:
                total += 2 * o.block_bytes(env)
        return total

    def vmem_bytes(self, *, n_blocks: int, block: int, cap: int,
                   b: int = 1) -> int:
        """Total VMEM working set for a concrete configuration."""
        n_pad = n_blocks * block
        return (int(round(self.per_vertex_bytes(b) * n_pad))
                + self.fixed_bytes(block=block, cap=cap, b=b))

    def max_vertices_per_core(self, *, block: int = 256, cap: int = 1024,
                              b: int = 1,
                              budget: int = VMEM_BYTES) -> int | None:
        """Largest padded vertex count whose whole-state working set fits the
        budget (block-aligned; ``None`` when nothing scales with vertices —
        e.g. the Jacobi kernel streams every vertex-shaped operand)."""
        pv = self.per_vertex_bytes(b)
        if pv <= 0:
            return None
        avail = budget - self.fixed_bytes(block=block, cap=cap, b=b)
        if avail <= 0:
            return 0
        return (int(avail // pv) // block) * block

    def check_budget(self, n_vertices: int, *, block: int = 256,
                     cap: int = 1024, b: int = 1,
                     budget: int = VMEM_BYTES) -> list[Finding]:
        """Flag a configuration whose working set exceeds the VMEM budget."""
        n_blocks = -(-max(int(n_vertices), 1) // block)
        need = self.vmem_bytes(n_blocks=n_blocks, block=block, cap=cap, b=b)
        if need <= budget:
            return []
        return [Finding(
            "vmem", self.kernel, "budget-overflow",
            f"{n_vertices} vertices (block={block}, b={b}) need "
            f"{need / 2**20:.1f} MiB of VMEM > {budget / 2**20:.1f} MiB "
            f"budget; max is {self.max_vertices_per_core(block=block, cap=cap, b=b)} "
            f"vertices/core — shard via repro.core.distributed first",
        )]

    def to_dict(self) -> dict:
        return {
            "kernel": self.kernel,
            "grid": list(self.grid),
            "per_vertex_bytes_expr": self.per_vertex_expr(),
            "per_vertex_bytes_b1": self.per_vertex_bytes(1),
            "max_vertices_per_core_b1": self.max_vertices_per_core(),
            "operands": [
                {"name": o.name, "kind": o.kind,
                 "shape": [str(d) for d in o.shape],
                 "block_shape": (None if o.block_shape is None
                                 else [str(d) for d in o.block_shape]),
                 "dtype": o.dtype, "resident": o.resident}
                for o in self.operands
            ],
            "findings": [f.to_dict() for f in self.findings],
        }


# ---------------------------------------------------------------------------
# Capture: record the grid spec without executing the kernel
# ---------------------------------------------------------------------------


class _Captured:
    def __init__(self):
        self.grid_spec = None
        self.out_shape = None
        self.operand_shapes = None


def capture_grid_spec(fn: Callable, args: Sequence[Any], **static) -> Any:
    """Call ``fn(*args, **static)`` with ``pl.pallas_call`` monkeypatched to
    record its grid spec instead of compiling/executing anything.

    ``fn`` may be a plain function or a ``jax.jit`` wrapper (its
    ``__wrapped__`` is used); ``args`` are typically ``ShapeDtypeStruct``\\ s,
    traced abstractly (``jax.eval_shape``) so the wrappers' own reshapes
    around the ``pallas_call`` work without data.  Returns ``(grid_spec,
    out_shape, operand_shapes)``: the grid spec exposes ``grid``,
    ``in_specs``, ``out_specs``, ``scratch_shapes``, ``num_scalar_prefetch``;
    ``operand_shapes`` are the ``(shape, dtype)`` of the pallas_call's
    operands, in its argument order."""
    cap = _Captured()

    def fake_pallas_call(kernel, *, grid_spec=None, out_shape=None, **_kw):
        cap.grid_spec = grid_spec
        cap.out_shape = out_shape
        def call(*call_args):
            cap.operand_shapes = [(tuple(a.shape), a.dtype) for a in call_args]
            return jnp.zeros(out_shape.shape, out_shape.dtype)

        return call

    target = getattr(fn, "__wrapped__", fn)
    orig = pl.pallas_call
    pl.pallas_call = fake_pallas_call
    try:
        jax.eval_shape(functools.partial(target, **static), *args)
    finally:
        pl.pallas_call = orig
    if cap.grid_spec is None:
        raise RuntimeError(f"{fn} never invoked pl.pallas_call")
    return cap.grid_spec, cap.out_shape, cap.operand_shapes


def _index_map_samples(grid_spec, t_values, n_blocks: int):
    """Prefetch-content samples for index-map evaluation: all-zero, all-max,
    and a mixed non-decreasing dst assignment — the extremes any in-contract
    tile->block map can produce."""
    T = len(t_values)
    lo = np.zeros(T, np.int32)
    hi = np.full(T, n_blocks - 1, np.int32)
    mixed = np.minimum(np.arange(T, dtype=np.int32) % n_blocks, n_blocks - 1)
    return [(lo, lo), (hi, hi), (mixed, np.sort(mixed))]


def analyze_grid_spec(grid_spec, arg_shapes: Sequence, operand_names:
                      Sequence[str], *, kernel: str,
                      out_shape=None) -> KernelReport:
    """Turn a captured grid spec + the symbolic argument shapes into a
    :class:`KernelReport` — residency classification, symbolic operand
    table, and index-map range findings.

    ``arg_shapes`` are the (shape, dtype) sources in pallas_call argument
    order (prefetch args first); ``operand_names`` name them in the same
    order, with the output appended last.
    """
    nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0))
    in_specs = list(grid_spec.in_specs)
    out_specs = grid_spec.out_specs
    out_list = list(out_specs) if isinstance(out_specs, (list, tuple)) else [out_specs]
    out_shapes = (list(out_shape) if isinstance(out_shape, (list, tuple))
                  else [out_shape])
    grid = tuple(grid_spec.grid)
    findings: list[Finding] = []

    expected = nsp + len(in_specs) + len(out_list)
    if len(operand_names) != expected:
        findings.append(Finding(
            "vmem", kernel, "operand-count-drift",
            f"analyzer names {len(operand_names)} operands but the kernel "
            f"takes {expected} (= {nsp} prefetch + {len(in_specs)} inputs + "
            f"{len(out_list)} outputs) — update repro.analysis.vmem's "
            f"operand table for this kernel",
        ))

    T = _eval_dim(grid[0], SYMBOLS) if grid else 1
    n_blocks = SYMBOLS["n_blocks"]
    t_values = list(range(T))
    samples = _index_map_samples(grid_spec, t_values, n_blocks)

    operands: list[Operand] = []

    def _name(i: int) -> str:
        return operand_names[i] if i < len(operand_names) else f"operand{i}"

    # prefetch scalars: SMEM, named for the table but excluded from VMEM
    for i in range(nsp):
        shp, dt = arg_shapes[i]
        operands.append(Operand(_name(i), "prefetch", _symbolize(shp), None,
                                str(np.dtype(dt)), np.dtype(dt).itemsize,
                                resident=True))

    def _classify(spec, full_shape, dt, name, kind) -> Operand:
        bs = tuple(spec.block_shape)
        outputs = set()
        ok = True
        nblocks_per_dim = [max(1, -(-int(full_shape[d]) // _eval_dim(bs[d], {})))
                           for d in range(len(bs))]
        for sb, db in samples:
            for t in t_values:
                idx = spec.index_map(t, sb, db)
                idx = tuple(int(x) for x in (idx if isinstance(idx, tuple)
                                             else (idx,)))
                outputs.add(idx)
                for d, x in enumerate(idx):
                    if not (0 <= x < nblocks_per_dim[d]):
                        ok = False
        if not ok:
            findings.append(Finding(
                "vmem", kernel, "index-map-out-of-range",
                f"operand {name!r}: index map can address block index "
                f"outside [0, {nblocks_per_dim}) for full shape "
                f"{_symbolize(full_shape)} / block {_symbolize(bs)}",
            ))
        return Operand(name, kind, _symbolize(full_shape), _symbolize(bs),
                       str(np.dtype(dt)), np.dtype(dt).itemsize,
                       resident=(len(outputs) == 1))

    for i, spec in enumerate(in_specs):
        shp, dt = arg_shapes[nsp + i]
        if spec.block_shape is None:  # whole array in SMEM (scalar params)
            operands.append(Operand(_name(nsp + i), "smem", _symbolize(shp),
                                    None, str(np.dtype(dt)),
                                    np.dtype(dt).itemsize, resident=True))
            continue
        operands.append(_classify(spec, shp, dt, _name(nsp + i), "input"))

    for j, (spec, osh) in enumerate(zip(out_list, out_shapes)):
        shp = tuple(osh.shape) if osh is not None else tuple(spec.block_shape)
        dt = osh.dtype if osh is not None else np.float32
        operands.append(_classify(spec, shp, dt, _name(nsp + len(in_specs) + j),
                                  "output"))

    for k, scratch in enumerate(getattr(grid_spec, "scratch_shapes", ()) or ()):
        shp = tuple(getattr(scratch, "shape", ()))
        dt = getattr(scratch, "dtype", np.float32)
        operands.append(Operand(f"scratch{k}", "scratch", _symbolize(shp),
                                _symbolize(shp), str(np.dtype(dt)),
                                np.dtype(dt).itemsize, resident=True))

    return KernelReport(kernel=kernel, grid=_symbolize(grid),
                        operands=operands, findings=findings)


# ---------------------------------------------------------------------------
# The real kernel family
# ---------------------------------------------------------------------------


def _S(*dims, dtype=np.float32):
    return jax.ShapeDtypeStruct(tuple(_eval_dim(d, SYMBOLS) for d in dims), dtype)


def _family_specs() -> dict[str, tuple]:
    """Symbolic calls of the three kernels: the wrapper, its arguments in
    signature order, and the operand names in **pallas_call order**
    (prefetch first, output last).  The operand shapes themselves are
    captured from the call, so a layout change needs no edit here, and a
    signature change shows up as an ``operand-count-drift`` finding rather
    than silently skewing the table."""
    from repro.kernels.spmv import kernel as K

    tiles = (_S("T", "cap", dtype=np.int32), _S("T", "cap", dtype=np.int32),
             _S("T", "cap"), _S("T", "cap"))
    maps = (_S("T", dtype=np.int32), _S("T", dtype=np.int32))
    vertex = _S("n_blocks", "block")
    panel = _S("n_blocks", "b", "block")
    tile_names = ["tiles_src_local", "tiles_dst_local", "tiles_valid",
                  "tiles_weight"]
    return {
        "spmv_blocked": (
            K.spmv_blocked, (vertex,) + tiles[:3] + maps,
            ["tile_src_block", "tile_dst_block", "contrib_blocks"]
            + tile_names[:3] + ["acc_blocks"]),
        "spmv_gs_pass": (
            K.spmv_gs_pass, (vertex,) * 5 + (_S(1, 3),) + tiles + maps,
            ["tile_src_block", "tile_dst_block", "params", "pr_blocks",
             "inv_out_blocks", "vmask_blocks", "bias_blocks", "frozen_blocks"]
            + tile_names + ["pr_state"]),
        "spmv_gs_pass_multi": (
            K.spmv_gs_pass_multi,
            (panel, vertex, vertex, _S(1, "b"), panel, _S(1, 1)) + tiles + maps,
            ["tile_src_block", "tile_dst_block", "params", "pr_blocks",
             "inv_out_blocks", "vmask_blocks", "frozen_rows", "base_blocks"]
            + tile_names + ["pr_state"]),
    }


@functools.lru_cache(maxsize=1)
def analyze_kernels() -> dict[str, KernelReport]:
    """Capture + analyze the whole SpMV kernel family (cached — the capture
    costs one Python call per kernel, no compilation)."""
    reports = {}
    for name, (fn, args, names) in _family_specs().items():
        gs, out_shape, operand_shapes = capture_grid_spec(
            fn, args, block=SYMBOLS["block"])
        reports[name] = analyze_grid_spec(gs, operand_shapes, names,
                                          kernel=name, out_shape=out_shape)
    return reports


def vmem_findings() -> list[Finding]:
    """All findings of the VMEM pass over the real kernel family, including
    a self-consistency check that each whole-state kernel's own computed
    maximum actually fits the budget."""
    out: list[Finding] = []
    for rep in analyze_kernels().values():
        out.extend(rep.findings)
        mx = rep.max_vertices_per_core()
        if mx is not None and mx > 0:
            need = rep.vmem_bytes(n_blocks=mx // 256, block=256, cap=1024)
            if need > VMEM_BYTES:
                out.append(Finding(
                    "vmem", rep.kernel, "budget-inconsistent",
                    f"computed max {mx} vertices/core needs {need} B > "
                    f"{VMEM_BYTES} B", ))
    return out


def variant_vmem(variant, *, block: int = 256, cap: int = 1024,
                 b: int = 1) -> dict | None:
    """The analyzer's VMEM estimate for one registry variant (``None`` for
    non-Pallas backends) — recorded by ``bench_variants --json`` so every
    BENCH artifact carries the budget its kernel was certified under."""
    if getattr(variant, "backend", None) != "pallas":
        return None
    if variant.name.startswith("ppr"):
        kernel = "spmv_gs_pass_multi"
    elif variant.schedule in ("nosync", "adaptive"):
        # the adaptive schedule drives the same GS pass, block-frozen
        kernel = "spmv_gs_pass"
    else:
        kernel = "spmv_blocked"
    rep = analyze_kernels()[kernel]
    return {
        "kernel": kernel,
        "vmem_bytes_per_vertex": rep.per_vertex_bytes(b),
        "vmem_bytes_per_vertex_expr": rep.per_vertex_expr(),
        "vmem_max_vertices_per_core": rep.max_vertices_per_core(
            block=block, cap=cap, b=b),
    }


# ---------------------------------------------------------------------------
# Generated docs table (docs/KERNELS.md embeds this between markers)
# ---------------------------------------------------------------------------

DOCS_BEGIN = "<!-- generated by `python -m repro.analysis` (vmem pass): begin -->"
DOCS_END = "<!-- generated by `python -m repro.analysis` (vmem pass): end -->"


def kernels_markdown(*, block: int = 256, cap: int = 1024) -> str:
    """The VMEM operand/budget table docs/KERNELS.md embeds — regenerate
    with ``python -m repro.analysis --write-docs-table`` after any kernel
    signature change (``scripts/docs_check.py`` diffs it)."""
    reps = analyze_kernels()
    lines = [
        DOCS_BEGIN,
        "",
        "| kernel | resident operands (whole pass) | streamed / grid step "
        "| B/vertex | max vertices/core |",
        "|---|---|---|---|---|",
    ]
    for name, rep in reps.items():
        resident = [o.name for o in rep.operands
                    if o.resident and o.kind in ("input", "output")
                    and o.scales_with_vertices()]
        streamed = [o.name for o in rep.operands
                    if not o.resident and o.kind in ("input", "output")]
        mx = rep.max_vertices_per_core(block=block, cap=cap)
        mx_s = "streaming (no whole-state residency)" if mx is None else f"~{mx:,}"
        lines.append(
            f"| `{name}` | {', '.join(f'`{r}`' for r in resident) or '—'} "
            f"| {', '.join(f'`{s}`' for s in streamed) or '—'} "
            f"| {rep.per_vertex_expr()} | {mx_s} |")
    gs = reps["spmv_gs_pass"]
    multi = reps["spmv_gs_pass_multi"]
    lines += [
        "",
        f"Budget: {VMEM_BYTES // 2**20} MiB/core, v5e's default scoped VMEM "
        f"(the kernels raise their scoped limit from the operand sizes, up to "
        f"the core's 128 MiB); resident operands are single-buffered, streamed "
        f"rows double-buffered (2 blocks in flight); scalar-prefetch maps and "
        f"scalar params live in SMEM.  At `block={block}`, `cap={cap}` the "
        f"global GS pass keeps "
        f"{gs.per_vertex_expr()} B/vertex resident → "
        f"**~{gs.max_vertices_per_core(block=block, cap=cap):,} vertices/"
        f"core**; the multi-vector pass keeps {multi.per_vertex_expr()} "
        f"B/vertex (b = batch rows) → e.g. "
        f"~{multi.max_vertices_per_core(block=block, cap=cap, b=8):,} at "
        f"b=8.",
        DOCS_END,
    ]
    return "\n".join(lines)
