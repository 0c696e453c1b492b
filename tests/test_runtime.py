"""Start-up settings (``repro.launch.runtime``) and the chip smoke's refusal
to run without a chip."""

import json
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import runtime


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test that sets it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_environment_stands(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    # JAX read the variable itself; the code sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_repo_path(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(runtime.REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert (runtime.REPO_ROOT / "pyproject.toml").is_file()
    # the same path on every call: no temporary name, pid or time in it
    assert runtime.enable_compile_cache() == path


def test_cache_dir_is_gitignored():
    lines = (runtime.REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in lines


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(runtime.REPO_ROOT / "chip_smoke.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
