"""The persistent compile cache lives in one fixed directory per checkout."""
from pathlib import Path

import jax

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parent.parent


def _with_cache_dir_restored(fn):
    was = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_default_cache_dir_is_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"

    def run():
        got = enable_compile_cache()
        return got, jax.config.jax_compilation_cache_dir

    got, cfg = _with_cache_dir_restored(run)
    assert got == cfg == str(REPO / ".jax_cache")


def test_env_cache_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def run():
        before = jax.config.jax_compilation_cache_dir
        got = enable_compile_cache()
        return got, before, jax.config.jax_compilation_cache_dir

    got, before, after = _with_cache_dir_restored(run)
    assert got == str(tmp_path)
    assert after == before  # nothing set in code
