"""Resumable out-of-core build pipeline: generate → reorder → layout.

The staged path from "nothing" to a solve-ready on-disk graph store
(:mod:`repro.graphs.store`), sized so a billion-edge build is bounded-memory
and interruptible at every step:

1. **generate** — streaming R-MAT: each bounded edge chunk is drawn from its
   deterministic slice of the random stream (:func:`repro.graphs.rmat.rmat_chunk`),
   sorted, pre-deduped, and spilled to disk; a k-way external merge then
   writes the dst-sorted ``raw/`` store.  The full edge list is never
   co-resident — peak RAM is O(chunk_edges + n).
2. **reorder** — a locality ordering (:mod:`repro.graphs.reorder`; BFS by
   default) is computed on the memmap-backed raw store and the store is
   rewritten under the permutation (chunked external re-sort) into
   ``reordered/``, recording ``perm`` so ranks un-permute to original ids.
3. **layout** — partition boundaries and blocked-tile occupancy statistics
   are derived in one streaming pass and written as ``LAYOUT.json`` inside
   the final store.

Progress lives in ``PIPELINE.json`` (atomic rewrite after every chunk and
stage, a durable latest-pointer): a
killed build resumes exactly where it stopped — completed stages are
skipped via their store manifests, and a partially generated stage skips
every spill chunk whose CRC still matches its record.  Chunk generation is
deterministic per ``(seed, chunk index)``, so an interrupted-and-resumed
build is **bit-identical** to an uninterrupted one (pinned by
tests/test_store.py).
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import time
from typing import Callable, Optional, Sequence

import numpy as np

from repro.graphs.csr import blocked_tile_stats
from repro.graphs.reorder import ORDERS, compute_order, invert_perm
from repro.graphs.rmat import rmat_chunk, rmat_vertex_perm
from repro.graphs.store import (
    GraphStore,
    SpillSet,
    StoreWriter,
    is_store,
    merge_spill_chunks,
    write_spill_chunk,
)

STAGES = ("generate", "reorder", "layout")
PIPELINE_FILE = "PIPELINE.json"
PIPELINE_VERSION = 1


@dataclasses.dataclass(frozen=True)
class BuildConfig:
    """Parameters of one pipeline run — persisted into ``PIPELINE.json`` so
    a resume with different parameters is rejected instead of silently
    producing a mixed store.

    ``fold_n`` folds generated vertex ids modulo a non-power-of-two target
    (the dataset surrogates of :mod:`repro.graphs.datasets`); the stored
    graph then has ``fold_n`` vertices.  ``n_edges`` defaults to
    ``avg_degree · 2**scale``.
    """

    scale: int
    avg_degree: int = 8
    n_edges: Optional[int] = None
    a: float = 0.57
    b: float = 0.19
    c: float = 0.19
    seed: int = 0
    fold_n: Optional[int] = None
    dedupe: bool = True
    chunk_edges: int = 1 << 21
    order: str = "bfs"
    threads: int = 56
    block: int = 256
    tile_cap: int = 1024

    def __post_init__(self):
        if self.order not in ORDERS:
            raise ValueError(f"order {self.order!r} not in {ORDERS}")

    @property
    def n(self) -> int:
        return self.fold_n if self.fold_n is not None else 1 << self.scale

    @property
    def total_edges(self) -> int:
        return (self.n_edges if self.n_edges is not None
                else self.avg_degree * (1 << self.scale))

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "BuildConfig":
        return cls(**d)


# ---------------------------------------------------------------------------
# Progress file (a durable latest-pointer, rewritten atomically)
# ---------------------------------------------------------------------------


def _progress_path(out_dir: str) -> str:
    return os.path.join(out_dir, PIPELINE_FILE)


def load_progress(out_dir: str) -> Optional[dict]:
    path = _progress_path(str(out_dir))
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _save_progress(out_dir: str, progress: dict) -> None:
    path = _progress_path(out_dir)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(progress, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _stage_dir(out_dir: str, name: str) -> str:
    return os.path.join(out_dir, name)


def raw_store_path(out_dir: str) -> str:
    return _stage_dir(str(out_dir), "raw")

def reordered_store_path(out_dir: str) -> str:
    return _stage_dir(str(out_dir), "reordered")


def final_store_path(out_dir: str) -> str:
    """The store a solve should load: reordered when that stage produced
    one, raw otherwise."""
    out_dir = str(out_dir)
    if is_store(reordered_store_path(out_dir)):
        return reordered_store_path(out_dir)
    return raw_store_path(out_dir)


# ---------------------------------------------------------------------------
# Stages
# ---------------------------------------------------------------------------


def _generate_stage(out_dir: str, cfg: BuildConfig, progress: dict,
                    log: Callable[[str], None]) -> dict:
    raw_dir = raw_store_path(out_dir)
    state = progress["stages"].setdefault("generate", {"chunks": {}})
    spill = SpillSet(os.path.join(out_dir, "chunks"))
    total = cfg.total_edges
    n_chunks = -(-total // cfg.chunk_edges) if total else 0
    perm = rmat_vertex_perm(cfg.scale, total, cfg.seed)
    reused = 0
    for ci in range(n_chunks):
        rec = state["chunks"].get(str(ci))
        if spill.valid(ci, rec):
            reused += 1
            continue
        lo = ci * cfg.chunk_edges
        hi = min(lo + cfg.chunk_edges, total)
        src, dst = rmat_chunk(cfg.scale, total, lo, hi, a=cfg.a, b=cfg.b,
                              c=cfg.c, seed=cfg.seed, perm=perm)
        if cfg.fold_n is not None:
            src = (src % cfg.fold_n).astype(np.int32)
            dst = (dst % cfg.fold_n).astype(np.int32)
        rec = write_spill_chunk(spill.chunk_path(ci), src, dst,
                                dedupe=cfg.dedupe)
        state["chunks"][str(ci)] = rec
        _save_progress(out_dir, progress)  # chunk-granular resume point
    if reused:
        log(f"generate: resumed, reusing {reused}/{n_chunks} spill chunks")

    writer = StoreWriter(raw_dir, cfg.n, weighted=False)
    merge_spill_chunks([spill.chunk_path(ci) for ci in range(n_chunks)],
                       cfg.n, writer, dedupe=cfg.dedupe)
    store = writer.finalize(order="none",
                            extra={"config": cfg.to_dict(), "stage": "generate"})
    spill.cleanup()
    return {"store": raw_dir, "n": store.n, "m": store.m}


def _reorder_stage(out_dir: str, cfg: BuildConfig, progress: dict,
                   log: Callable[[str], None]) -> dict:
    raw = GraphStore(raw_store_path(out_dir))
    g = raw.graph(mmap=True)
    perm = compute_order(g, cfg.order, seed=cfg.seed)
    inv = invert_perm(perm)

    state = progress["stages"].setdefault("reorder", {"chunks": {}})
    spill = SpillSet(os.path.join(out_dir, "reorder_chunks"))
    n_chunks = 0
    reused = 0
    for lo, src, dst, w in g.edge_chunks(cfg.chunk_edges):
        ci = lo // cfg.chunk_edges
        n_chunks = ci + 1
        if spill.valid(ci, state["chunks"].get(str(ci))):
            reused += 1
            continue
        rec = write_spill_chunk(
            spill.chunk_path(ci),
            np.asarray(perm[src], dtype=np.int32),
            np.asarray(perm[dst], dtype=np.int32),
            weights=w,
        )
        state["chunks"][str(ci)] = rec
        _save_progress(out_dir, progress)
    if reused:
        log(f"reorder: resumed, reusing {reused}/{n_chunks} spill chunks")

    prev = raw.perm()
    total_perm = perm if prev is None else perm[prev]
    writer = StoreWriter(reordered_store_path(out_dir), g.n,
                         weighted=g.weights is not None)
    merge_spill_chunks([spill.chunk_path(ci) for ci in range(n_chunks)],
                       g.n, writer, dedupe=False)
    store = writer.finalize(
        out_degree=np.asarray(g.out_degree)[inv],
        bias=None if g.bias is None else np.asarray(g.bias)[inv],
        perm=total_perm,
        order=cfg.order,
        extra={"config": cfg.to_dict(), "stage": "reorder"},
    )
    spill.cleanup()
    return {"store": store.path, "order": cfg.order, "n": store.n,
            "m": store.m}


def _layout_stage(out_dir: str, cfg: BuildConfig, progress: dict,
                  log: Callable[[str], None]) -> dict:
    store = GraphStore(final_store_path(out_dir))
    g = store.graph(mmap=True)
    stats = blocked_tile_stats(g, block=cfg.block, tile_cap=cfg.tile_cap,
                               chunk_edges=cfg.chunk_edges)
    bounds = g.partition_ranges(cfg.threads)
    edges_per_part = np.diff(np.asarray(g.in_ptr)[bounds]).tolist()
    layout = {
        "threads": cfg.threads,
        "partition_bounds": bounds.tolist(),
        "partition_edges": edges_per_part,
        "tile_stats": stats,
    }
    store.write_layout(layout)
    return {"store": store.path, "occupancy": stats["occupancy"],
            "n_tiles": stats["n_tiles"]}


_STAGE_FNS = {
    "generate": _generate_stage,
    "reorder": _reorder_stage,
    "layout": _layout_stage,
}


def _stage_complete(out_dir: str, name: str, progress: dict) -> bool:
    done = progress["stages"].get(name, {}).get("done", False)
    if name == "generate":
        return done and is_store(raw_store_path(out_dir))
    if name == "reorder":
        return done and is_store(reordered_store_path(out_dir))
    if name == "layout":
        return done and GraphStore(final_store_path(out_dir)).layout() is not None
    return done


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def run_pipeline(
    out_dir: str,
    cfg: Optional[BuildConfig] = None,
    stages: Optional[Sequence[str]] = None,
    log: Callable[[str], None] = print,
) -> dict:
    """Run (or resume) the staged build under ``out_dir``.

    ``stages`` selects a subset (canonical order is enforced; a stage whose
    input stage has not completed raises).  Completed stages are skipped —
    calling again after an interrupt, or with a later-stage subset, resumes.
    ``cfg=None`` resumes with the recorded config; passing a config that
    differs from the recorded one raises (delete the directory to rebuild).

    Returns ``{"out", "store", "stages": {name: {..., "wall_s"}}}``.
    """
    out_dir = str(out_dir)
    os.makedirs(out_dir, exist_ok=True)
    progress = load_progress(out_dir)
    if progress is None:
        if cfg is None:
            raise ValueError(f"{out_dir}: no pipeline to resume and no "
                             "config given")
        progress = {"version": PIPELINE_VERSION, "config": cfg.to_dict(),
                    "stages": {}}
        _save_progress(out_dir, progress)
    else:
        recorded = BuildConfig.from_dict(progress["config"])
        if cfg is None:
            cfg = recorded
        elif cfg != recorded:
            raise ValueError(
                f"{out_dir}: pipeline was started with a different config; "
                "resume without overriding it or rebuild in a fresh directory")

    selected = list(stages) if stages is not None else list(STAGES)
    unknown = set(selected) - set(STAGES)
    if unknown:
        raise ValueError(f"unknown stage(s) {sorted(unknown)}; "
                         f"expected from {STAGES}")
    selected = [s for s in STAGES if s in selected]
    if cfg.order == "none" and "reorder" in selected:
        selected.remove("reorder")  # identity reorder: raw IS final

    results: dict = {}
    for name in selected:
        idx = STAGES.index(name)
        for dep in STAGES[:idx]:
            if dep == "reorder" and cfg.order == "none":
                continue
            if not _stage_complete(out_dir, dep, progress):
                raise ValueError(f"stage {name!r} needs {dep!r} first "
                                 f"(run it or pass stages={list(STAGES)})")
        if _stage_complete(out_dir, name, progress):
            log(f"{name}: already complete, skipping")
            results[name] = dict(progress["stages"][name],
                                 skipped=True)
            continue
        t0 = time.perf_counter()
        info = _STAGE_FNS[name](out_dir, cfg, progress, log)
        info["wall_s"] = round(time.perf_counter() - t0, 3)
        info["done"] = True
        state = progress["stages"].setdefault(name, {})
        state.update(info)
        state.pop("chunks", None)  # spill records are dead once merged
        _save_progress(out_dir, progress)
        log(f"{name}: done in {info['wall_s']:.2f}s "
            + " ".join(f"{k}={v}" for k, v in info.items()
                       if k not in ("wall_s", "done", "chunks")))
        results[name] = info
    return {"out": out_dir, "store": final_store_path(out_dir),
            "stages": results}


def reorder_store(src_store: str, out_dir: str, order: str = "bfs",
                  seed: int = 0, chunk_edges: int = 1 << 21,
                  threads: int = 56, block: int = 256, tile_cap: int = 1024,
                  log: Callable[[str], None] = print) -> dict:
    """Reorder + layout an **existing** store (e.g. a cached dataset) into a
    fresh pipeline directory, without a generate stage: the store is linked
    in as the raw stage and the ordinary resume machinery runs the rest."""
    out_dir = str(out_dir)
    os.makedirs(out_dir, exist_ok=True)
    src = GraphStore(src_store)
    raw_dir = raw_store_path(out_dir)
    if not is_store(raw_dir):
        shutil.copytree(src.path, raw_dir, dirs_exist_ok=True)
    g = src.graph(mmap=True)
    cfg = BuildConfig(
        scale=max(1, int(np.ceil(np.log2(max(g.n, 2))))),
        n_edges=g.m, fold_n=g.n, dedupe=False, order=order, seed=seed,
        chunk_edges=chunk_edges, threads=threads, block=block,
        tile_cap=tile_cap,
    )
    progress = load_progress(out_dir)
    if progress is None:
        progress = {"version": PIPELINE_VERSION, "config": cfg.to_dict(),
                    "stages": {"generate": {"done": True, "store": raw_dir,
                                            "adopted": src.path}}}
        _save_progress(out_dir, progress)
    return run_pipeline(out_dir, stages=["reorder", "layout"], log=log)
