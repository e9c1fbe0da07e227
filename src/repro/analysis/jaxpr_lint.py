"""Jaxpr lint: trace every registry variant's solve and check the traced
program against the schedule contract its registry metadata declares.

The paper's no-sync claim is a property of the *schedule*, so it is
decidable from the traced program: a variant registered ``schedule="nosync"``
must not execute a collective that synchronizes workers every sweep, a
device path must never silently promote to float64 (TPUs emulate it at
~1/10th throughput — any f64 on the hot path is a leak from a numpy
default), and nothing on the sweep may bounce through the host (callbacks)
or move arrays between devices mid-solve.

Mechanics: each variant is built on a tiny synthetic graph (16 vertices —
tracing cost is shape-independent) and its ``run`` is traced with
``jax.make_jaxpr`` to a closed jaxpr, which is walked recursively (pjit /
scan / while / shard_map bodies live in ``eqn.params``).  Variants whose
build returns a STIC-D :class:`~repro.core.solver.PlannedBundle` are traced
through the *inner* variant on the core bundle — the plan wrapper itself is
host-side numpy by design (pre/post contraction), not part of the sweep.

``lint_jaxpr`` is public and pure so tests can aim it at deliberately-broken
functions without touching the registry.
"""
from __future__ import annotations

import functools
from typing import Iterable

import jax
import numpy as np

from repro.analysis.findings import Finding

# Cross-worker collectives: any of these inside a nosync schedule is a
# synchronization point the metadata claims does not exist.
COLLECTIVE_PRIMS = frozenset({
    "all_gather", "psum", "pmax", "pmin", "ppermute", "all_to_all",
    "reduce_scatter", "psum_scatter",
})

# Host round-trips: a device sweep that calls back into Python serializes on
# the host and voids the non-blocking cost model.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
})


def _iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every jaxpr nested in its equations' params."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            for sub in _as_jaxprs(val):
                yield from _iter_jaxprs(sub)


def _as_jaxprs(val) -> Iterable:
    if hasattr(val, "jaxpr"):  # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):  # raw Jaxpr
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _as_jaxprs(v)


def lint_jaxpr(jaxpr, *, target: str, schedule: str = "",
               check_float64: bool = True) -> list[Finding]:
    """Lint one (closed or raw) jaxpr against the schedule contract.

    Pure function of the traced program — the registry pass and the test
    fixtures both funnel through here.
    """
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    findings: list[Finding] = []
    collectives: set[str] = set()
    callbacks: set[str] = set()
    transfers = 0
    f64_eqns: list[str] = []

    for jx in _iter_jaxprs(inner):
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim in COLLECTIVE_PRIMS:
                collectives.add(prim)
            if prim in CALLBACK_PRIMS:
                callbacks.add(prim)
            if prim == "device_put":
                # jit-internal device_put carries devices=[None]; an actual
                # cross-device move names a concrete target device/sharding
                devices = eqn.params.get("devices", ())
                if any(d is not None for d in devices):
                    transfers += 1
            if check_float64:
                for v in eqn.outvars:
                    aval = getattr(v, "aval", None)
                    if getattr(aval, "dtype", None) == np.float64:
                        f64_eqns.append(prim)
                        break

    if f64_eqns:
        findings.append(Finding(
            "jaxpr", target, "float64-leak",
            f"traced program computes float64 on the device path "
            f"(primitives: {sorted(set(f64_eqns))}) — TPUs emulate f64; a "
            f"numpy default has leaked past the f32 boundary",
        ))
    if callbacks:
        findings.append(Finding(
            "jaxpr", target, "host-callback",
            f"device sweep round-trips through the host "
            f"({sorted(callbacks)}) — serializes on Python and voids the "
            f"non-blocking cost model",
        ))
    if transfers:
        findings.append(Finding(
            "jaxpr", target, "device-transfer",
            f"{transfers} explicit cross-device transfer(s) inside the "
            f"traced solve — state should be placed once, before the sweep",
        ))
    if collectives and schedule == "nosync":
        findings.append(Finding(
            "jaxpr", target, "collective-in-nosync",
            f"schedule metadata says 'nosync' but the traced program "
            f"synchronizes via {sorted(collectives)}",
        ))
    return findings


# ---------------------------------------------------------------------------
# Tracing the real registry
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _tiny_graph():
    from repro.graphs import rmat_graph

    return rmat_graph(scale=4, avg_degree=4, seed=7)


def trace_variant(name: str):
    """Build + trace one registry variant's solve to a closed jaxpr.

    Returns ``None`` for host-side (numpy-backend) variants — there is no
    device program to lint.  STIC-D planned variants are traced through
    their inner solver on the contracted core bundle.
    """
    from repro.core.solver import PlannedBundle, build_variant, get_variant

    v = get_variant(name)
    if v.backend == "numpy":
        return None
    opts = dict(threads=2, block=8, tile_cap=16, local_sweeps=2,
                send_fraction=0.5)
    v, bundle = build_variant(name, _tiny_graph(), **opts)
    run, target_bundle = v.run, bundle
    if isinstance(bundle, PlannedBundle):
        run, target_bundle = bundle.inner.run, bundle.bundle

    def solve():
        return run(target_bundle, threshold=1e-4, max_iter=3,
                   handle_dangling=True, **opts)

    return jax.make_jaxpr(solve)()


def jaxpr_findings(names: Iterable[str] | None = None) -> list[Finding]:
    """Lint every (device-backend) registry variant's traced solve."""
    from repro.core.solver import get_variant, list_variants

    out: list[Finding] = []
    for name in (names if names is not None else list_variants()):
        v = get_variant(name)
        try:
            jaxpr = trace_variant(name)
        except Exception as e:  # untraceable IS a finding, not a crash
            out.append(Finding(
                "jaxpr", name, "untraceable",
                f"variant could not be traced to a jaxpr: {type(e).__name__}: {e}",
            ))
            continue
        if jaxpr is None:
            continue
        out.extend(lint_jaxpr(jaxpr, target=name, schedule=v.schedule))
    return out


# The serving engine's batched step is live on the hot path of every query
# the runtime answers, and it is not a registry variant — lint it under the
# same contract the solvers carry: slot rounds are independent (nosync), f32
# end-to-end, no host round-trips inside the jitted step.
SERVING_BACKENDS = (
    ("jax", {}),
    ("pallas", dict(block=8, tile_cap=16)),
)


def serving_findings() -> list[Finding]:
    """Trace each serving backend's ``multi_step`` and lint it."""
    from repro.serving.ppr_engine import PPREngine

    out: list[Finding] = []
    for name, opts in SERVING_BACKENDS:
        target = f"serving_{name}"
        try:
            eng = PPREngine(_tiny_graph(), slots=2, iters_per_step=2,
                            backend=name, **opts)
            be = eng._backend
            jaxpr = jax.make_jaxpr(be.multi_step)(
                be.state, be.tele, np.zeros(eng.slots, dtype=bool))
        except Exception as e:
            out.append(Finding(
                "jaxpr", target, "untraceable",
                f"serving backend could not be traced to a jaxpr: "
                f"{type(e).__name__}: {e}",
            ))
            continue
        out.extend(lint_jaxpr(jaxpr, target=target, schedule="nosync"))
    return out
