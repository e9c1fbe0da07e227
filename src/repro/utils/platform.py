"""The backend the program runs on: how Pallas kernels execute there, and
where compiled programs are cached.

Pallas kernels compile for the TPU and run in the Pallas interpreter on the
CPU (tests and rehearsals).  :func:`pallas_interpret` is the one place that
choice is made, from ``jax.default_backend()``; no backend silently falls
back to the interpreter.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/src/repro/utils/platform.py → <repo>
REPO_ROOT = Path(__file__).resolve().parents[3]


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Whether Pallas kernels run interpreted.

    ``None`` decides from the backend: interpreted on ``cpu``, compiled on
    ``tpu``, and any other backend raises.  An explicit ``False`` always
    compiles (a compile-only check for a described TPU runs on the CPU
    backend); an explicit ``True`` is refused on a TPU, where it would hide
    the device behind the interpreter."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"no Pallas path for backend {backend!r}: run on a TPU, or on "
            f"the CPU with JAX_PLATFORMS=cpu")
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError("interpret=True on a TPU backend: the kernels would "
                         "run in the interpreter instead of on the chip")
    return bool(interpret)


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed.  Otherwise the cache goes to ``<repo>/.jax_cache``:
    a fixed path, because the path is part of the cache key.  Call before
    the first compilation — JAX fixes its cache when it first compiles."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
