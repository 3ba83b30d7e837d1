"""Entry-point plumbing: the compile-cache placement and chip_smoke.py's
refusal to run anywhere but on a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.compile_cache import REPO_CACHE_DIR, use_compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR.parent == Path(ROOT)


def test_chip_smoke_refuses_cpu():
    import chip_smoke                  # conftest puts the repo root on the path
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.require_tpu()
    assert exc.value.code not in (0, None)


def test_chip_smoke_script_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
