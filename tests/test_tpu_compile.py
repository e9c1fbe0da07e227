"""The SpMV kernels compile for a described TPU v5e at the chip smoke's
widths — what interpret mode cannot check (block alignment, VMEM, SMEM,
2-D contractions) is checked here without a chip.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import base64
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.spmv.kernel import (
    MAX_TILES,
    spmv_blocked,
    spmv_gs_pass,
    spmv_gs_pass_multi,
)

# chip_smoke.py's global layout: full webStanford at block 1024 / cap 128;
# full socEpinions1 at the registry's PPR defaults (block 256 / cap 1024,
# 8 slots); and the layout choose_layout picks for the Graph500 scale-16
# graph of bench/graph.py at seed 0 (block 640 / cap 128, 12,650 tiles)
WEB = dict(n_blocks=276, block=1024, T=76_371, cap=128)
SOC = dict(n_blocks=297, block=256, T=84_415, cap=1024, b=8)
CHOSEN = dict(n_blocks=103, block=640, T=12_650, cap=128, b=8)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _args(sharding, *, n_blocks, block, T, cap, b=None):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    vertex = s((n_blocks, block))
    tiles = (s((T, cap), jnp.int32), s((T, cap), jnp.int32), s((T, cap)), s((T, cap)))
    maps = (s((T,), jnp.int32), s((T,), jnp.int32))
    if b is None:
        return {
            "spmv_blocked": (vertex,) + tiles[:3] + maps,
            "spmv_gs_pass": (vertex,) * 5 + (s((1, 3)),) + tiles + maps,
        }
    panel = s((n_blocks, b, block))
    return {"spmv_gs_pass_multi":
            (panel, vertex, vertex, s((1, b)), panel, s((1, 1))) + tiles + maps}


@pytest.mark.parametrize("kernel,fn,widths", [
    ("spmv_blocked", spmv_blocked, WEB),
    ("spmv_gs_pass", spmv_gs_pass, WEB),
    ("spmv_gs_pass_multi", spmv_gs_pass_multi, SOC),
    ("spmv_gs_pass_multi", spmv_gs_pass_multi, CHOSEN),
])
def test_kernel_compiles_for_v5e(one_chip, kernel, fn, widths):
    args = _args(one_chip, **widths)[kernel]
    compiled = jax.jit(
        lambda *a: fn(*a, block=widths["block"], interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("extra", [0, 1])
def test_tile_limit_is_where_smem_fills(one_chip, extra):
    """MAX_TILES tiles compile; one more overflows the SMEM tile maps."""
    widths = dict(CHOSEN, T=MAX_TILES + extra)
    args = _args(one_chip, **widths)["spmv_gs_pass_multi"]
    lower = jax.jit(lambda *a: spmv_gs_pass_multi(
        *a, block=widths["block"], interpret=False)).lower(*args)
    if extra:
        with pytest.raises(Exception, match="smem"):
            lower.compile()
    else:
        assert "tpu_custom_call" in lower.compile().as_text()


def _nested_ops(op):
    """``op`` and every op nested in its regions."""
    yield op
    for region in op.regions:
        for block in region.blocks:
            for child in block.operations:
                yield from _nested_ops(child.operation)


def _mosaic_ops(lowered, name):
    """Operand types and attributes of the ops called ``name`` in the Mosaic
    module of each TPU kernel of a lowered program, read back from the
    custom call's serialized body."""
    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir
    from jaxlib.mlir.passmanager import PassManager

    def attr(op, key):
        return ir.StringAttr(op.attributes[key]).value

    bodies = [json.loads(attr(op, "backend_config"))["custom_call_config"]["body"]
              for op in _nested_ops(lowered.compiler_ir("stablehlo").operation)
              if op.name == "stablehlo.custom_call"
              and attr(op, "call_target_name") == "tpu_custom_call"]
    found = []
    with mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True
        for body in bodies:
            module = ir.Module.parse(base64.b64decode(body))
            PassManager.parse("builtin.module(mosaic-serde{serialize=false})"
                              ).run(module.operation)
            found += [([str(v.type) for v in op.operands],
                       {k: str(op.attributes[k]) for k in op.attributes})
                      for op in _nested_ops(module.operation) if op.name == name]
    return found


@pytest.mark.parametrize("kernel,fn,widths", [
    ("spmv_blocked", spmv_blocked, WEB),
    ("spmv_gs_pass", spmv_gs_pass, WEB),
    ("spmv_gs_pass_multi", spmv_gs_pass_multi, CHOSEN),
])
def test_tile_contraction_latches_bf16_one_hots(one_chip, kernel, fn, widths):
    """Each kernel's two tile contractions are bf16 × bf16 MXU matmuls with
    f32 accumulation (the split f32 operand against the bf16 one-hot), not
    f32 one-hots at HIGHEST precision, which the v5e latches six times."""
    args = _args(one_chip, **widths)[kernel]
    lowered = jax.jit(
        lambda *a: fn(*a, block=widths["block"], interpret=False)).lower(*args)
    matmuls = _mosaic_ops(lowered, "tpu.matmul")
    assert len(matmuls) == 2
    for operands, attrs in matmuls:
        lhs, rhs, acc = operands
        assert lhs.endswith("xbf16>") and rhs.endswith("xbf16>"), operands
        assert acc.endswith("xf32>"), operands
        assert "fp32" not in attrs.get("precision", ""), attrs
