"""Persistent compilation cache placement (launch/compile_cache.py):
``$JAX_COMPILATION_CACHE_DIR`` when set, else one fixed path inside the
checkout."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_wins(tmp_path, monkeypatch, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


@pytest.mark.parametrize("value", [None, ""], ids=["unset", "empty"])
def test_fixed_checkout_path_otherwise(value, monkeypatch,
                                       restore_cache_dir):
    if value is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, value)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    # the same on every call: the cache is found again next run
    assert compile_cache.enable_compile_cache() == path
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert (root / "chip_smoke.py").exists() and (root / "src").is_dir()
