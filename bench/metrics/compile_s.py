"""Seconds of set-up that JAX reported under ``/jax/core/compile/``
(tracing, lowering, compiling or loading from the persistent cache)."""


def read(run, trace):
    return run.facts["compile_s"]
