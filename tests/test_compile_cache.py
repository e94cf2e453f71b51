"""``repro.compile_cache``: JAX's persistent cache, in one place."""
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_env_dir_wins_and_sets_nothing(monkeypatch, tmp_path,
                                       restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # a second call (a later run in the same checkout) finds it again
    assert compile_cache.enable_compile_cache() == got
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_counts_compiles_that_use_the_cache(monkeypatch, tmp_path,
                                            restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    compile_cache.enable_compile_cache()
    before = compile_cache.compile_cache_counts()
    jax.jit(lambda x: x * 3 + 1).lower(1.0).compile()
    after = compile_cache.compile_cache_counts()
    assert set(after) == {"requests", "hits", "writes"}
    assert after["requests"] == before["requests"] + 1
