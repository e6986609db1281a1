"""common/compile_cache: the one place that decides where JAX's
persistent compilation cache lives (server, bench.py, chip_smoke.py)."""

import os

import jax

from elasticsearch_tpu.common import compile_cache


def test_env_set_is_left_alone(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself; the helper
    reports it and sets no other directory in code."""
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path / "elsewhere"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.compile_cache_dir() == (
        str(tmp_path / "elsewhere"), True
    )
    assert compile_cache.configure_compile_cache() == str(
        tmp_path / "elsewhere"
    )
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "elsewhere").exists()  # nothing made in code


def test_unset_is_one_fixed_directory_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        first = compile_cache.configure_compile_cache()
        second = compile_cache.configure_compile_cache()
        # fixed: no temporary name, pid or time in it — equal across
        # calls (and across processes, being a pure function of the
        # checkout's location)
        assert first == second == os.path.join(repo, ".jax_compile_cache")
        assert compile_cache.compile_cache_dir() == (first, False)
        assert jax.config.jax_compilation_cache_dir == first
        assert os.path.isdir(first)
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_compile_cache/" in f.read().split()
    finally:
        # tier-1 runs without a persistent cache: put it back
        jax.config.update("jax_compilation_cache_dir", before)
