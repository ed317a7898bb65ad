"""launch.compile: where the persistent compilation cache goes, and the
compile counter the trainer's no-recompile check reads."""
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile as lc

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_cache_dir_is_left_to_jax(monkeypatch, cache_config):
    monkeypatch.setenv(lc.CACHE_ENV, "/some/where")
    before = jax.config.jax_compilation_cache_dir
    assert lc.setup_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_and_ignored(monkeypatch, cache_config):
    monkeypatch.delenv(lc.CACHE_ENV, raising=False)
    path = lc.setup_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compile_counter_counts_misses_not_hits():
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7.0)
    with lc.CompileCounter() as first:
        f(x).block_until_ready()
    with lc.CompileCounter() as again:
        f(x).block_until_ready()
    assert first.count == 1 and again.count == 0
    assert os.path.basename(lc.DEFAULT_CACHE_DIR) == ".jax_cache"
