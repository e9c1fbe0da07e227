import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(kind: str, name: str) -> dict:
    return json.loads((ROOT / "bench" / kind / f"{name}.json").read_text())


@pytest.fixture
def small_spec():
    """The spec of a configuration under a traffic mix (``"config.mix"``,
    as a cell is named), with the graph cut to ``scale``: the harness and
    the drivers unchanged, on a size the CPU runs in seconds (Pallas
    kernels interpreted)."""

    def make(cell: str, scale: int = 8, **mix):
        config, traffic = cell.split(".", 1)
        spec = {"config": copy.deepcopy(load("configs", config)),
                "mix": {**load("traffic", traffic), **mix}}
        spec["config"]["graph"]["scale"] = scale
        return spec

    return make
