"""Platform-derived settings: Pallas interpret mode, the persistent
compilation cache location, and `chip_smoke.py`'s refusal to run (or
report a result) without a TPU."""
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.core import enable_persistent_cache, make_engine
from repro.kernels import common

REPO = pathlib.Path(__file__).resolve().parents[1]


# ------------------------------------------------------------ interpret ---

@pytest.mark.parametrize("platform, expected", [("cpu", True),
                                                ("tpu", False)])
def test_default_interpret_follows_platform(monkeypatch, platform, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert common.default_interpret() is expected
    assert common.resolve_interpret(None) is expected
    # An explicit choice always wins (compiling for a described chip).
    assert common.resolve_interpret(not expected) is (not expected)


def test_default_interpret_raises_on_unknown_platform(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        common.default_interpret()


def test_default_interpret_is_true_in_the_test_process():
    assert jax.default_backend() == "cpu"
    assert common.default_interpret() is True


def test_engine_leaves_interpret_to_the_platform():
    assert make_engine("pallas").interpret is None
    assert make_engine("pallas", interpret=False).interpret is False


# -------------------------------------------------------- compile cache ---

@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_persistent_cache_honours_env(monkeypatch, tmp_path,
                                      restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_persistent_cache_fixed_checkout_path(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_persistent_cache()
    assert first == str(REPO / ".jax_cache")
    assert enable_persistent_cache() == first
    assert jax.config.jax_compilation_cache_dir == first


# ----------------------------------------------------------- chip_smoke ---

def _run_smoke(script: pathlib.Path, cwd: pathlib.Path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(cwd)}
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    proc = _run_smoke(REPO / "chip_smoke.py", REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_alone_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((REPO / "chip_smoke.py").read_text())
    proc = _run_smoke(lone, tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
