"""The entry points' compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set
(and no other directory set in code), else a fixed directory inside the
checkout on a TPU, and none on the CPU."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_env_dir_wins(monkeypatch, restore_cache_dir, tmp_path, backend):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_dir_on_tpu(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = compile_cache.configure()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_no_cache_on_cpu(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() is None
    assert jax.config.jax_compilation_cache_dir == before
