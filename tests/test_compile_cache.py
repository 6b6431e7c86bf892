"""The entry points' compile-cache location (``launch.compile_cache``)."""

import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _restoring(fn):
    before = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_directory_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def check():
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    _restoring(check)


def test_default_is_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def check():
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(_REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    _restoring(check)
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_entries_land_in_env_directory(tmp_path):
    """A compile after ``enable_compile_cache`` writes its entry under
    ``$JAX_COMPILATION_CACHE_DIR`` (fresh process: the cache is process
    state)."""
    env = dict(os.environ)
    env.update(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.path.join(_REPO, "src"))
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert os.listdir(tmp_path / "cache")
