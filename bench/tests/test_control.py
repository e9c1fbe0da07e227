"""The control at a size a test run holds: the plain reference in bfloat16,
put in the program's place, fails the cell's comparison, while the same
reference in float32 (the precision the configurations state) passes it."""
import jax.numpy as jnp
import pytest

from bench import control

CELLS = ["graph500-18.nosync-cold", "graph500-16.ppr-steady"]


def correct(checks) -> bool:
    return all(v <= lim for _, v, lim in checks)


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_refused(small_spec, cell):
    spec = small_spec(cell, scale=11)
    for seed in (3, 2**31 + 3):
        assert not correct(control.cell_checks(spec, seed, jnp.bfloat16, 50))


@pytest.mark.parametrize("cell", CELLS)
def test_float32_reference_passes(small_spec, cell):
    spec = small_spec(cell, scale=11)
    assert correct(control.cell_checks(spec, 3, jnp.float32, 50))
