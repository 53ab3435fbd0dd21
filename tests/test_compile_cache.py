"""The persistent compilation cache lives at one directory that never moves."""
import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_no_other_dir_is_set(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cache"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path / "cache")
    assert jax.config.jax_compilation_cache_dir == before


def test_without_env_var_the_dir_is_fixed_inside_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == jax.config.jax_compilation_cache_dir
    repo = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert first == str(repo / ".jax_cache")
    assert (repo / "pyproject.toml").exists()  # the checkout's root, not a temp dir
