"""Where the persistent compilation cache lives: the environment's
``JAX_COMPILATION_CACHE_DIR`` when set, otherwise ``<repo>/.jax_cache``."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = use_compile_cache()
    assert got == str(REPO / ".jax_cache") == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert use_compile_cache() == got            # same path every call


def test_env_dir_is_not_overridden(monkeypatch, restore_cache_dir,
                                   tmp_path):
    # JAX reads the variable when it is imported; emulate that here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_env_dir_receives_the_cache_entries(tmp_path):
    """In a fresh process with the variable set, a compile lands there."""
    cache = tmp_path / "cache"
    env = dict(os.environ,
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import use_compile_cache
        print(use_compile_cache())
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
    """)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == str(cache)
    assert any(cache.iterdir()), "no cache entry written"
