"""``chip_smoke.py`` refuses to run without a TPU, and the compile-cache
helper places JAX's persistent cache where the entry points expect."""
import os
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(**env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=full, capture_output=True, text=True,
                          timeout=300)


def test_smoke_fails_without_tpu():
    r = _run_smoke()
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_refuses_ref_kernels():
    r = _run_smoke(REPRO_PALLAS="off")
    assert r.returncode != 0
    assert "REPRO_PALLAS=off" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test changes it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_respects_env(monkeypatch, tmp_path, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
