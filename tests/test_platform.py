"""The backend decides how Pallas kernels run, and the chip smoke refuses to
run anywhere but on a TPU."""
import importlib.util
from pathlib import Path

import pytest

from repro.utils import platform

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("backend,interpret,expected", [
    ("cpu", None, True),
    ("cpu", True, True),
    ("cpu", False, False),  # compile-only checks for a described TPU
    ("tpu", None, False),
    ("tpu", False, False),
    ("tpu", True, ValueError),
    ("gpu", None, RuntimeError),
    ("gpu", False, RuntimeError),
])
def test_pallas_interpret_follows_backend(monkeypatch, backend, interpret, expected):
    monkeypatch.setattr(platform.jax, "default_backend", lambda: backend)
    if isinstance(expected, bool):
        assert platform.pallas_interpret(interpret) is expected
    else:
        with pytest.raises(expected):
            platform.pallas_interpret(interpret)


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_init_compile_cache_fixed_path_unless_env(monkeypatch, env):
    updates = {}
    monkeypatch.setattr(platform.jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = str(ROOT / ".jax_cache")
        assert platform.init_compile_cache() == path
        assert updates == {"jax_compilation_cache_dir": path}
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert platform.init_compile_cache() == env
        assert updates == {}  # JAX reads the variable itself


def test_chip_smoke_refuses_cpu(capsys):
    """On the CPU backend the smoke exits non-zero and prints no result."""
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""
