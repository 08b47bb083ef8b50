"""repro.compile_cache: where the persistent compilation cache lives."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         restore_cache_dir):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_directory_in_the_checkout(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert compile_cache.CHECKOUT_CACHE_DIR.parent.joinpath(
        "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.enable_compile_cache() == got   # same every call
    assert set(compile_cache.cache_events()) == {"hits", "misses"}
