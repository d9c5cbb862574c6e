"""The entry points' persistent compilation cache
(``repro.launch.compile_cache``): an operator's
``JAX_COMPILATION_CACHE_DIR`` is left alone; otherwise the cache goes
to the fixed ``<checkout>/.jax_cache``."""
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def jax_cache_config():
    """Restore JAX's cache settings after the test."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_env_directory_is_left_alone(monkeypatch, tmp_path,
                                     jax_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == tmp_path
    # JAX reads the variable itself; nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("value", [None, ""])
def test_default_is_fixed_in_checkout(monkeypatch, value, jax_cache_config):
    if value is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, value)
    got = compile_cache.enable_compile_cache()
    assert got == CHECKOUT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(got)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # the same path on every call: no pid, time or temporary name
    assert compile_cache.enable_compile_cache() == got


def test_cache_directory_is_ignored_by_git():
    lines = (CHECKOUT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in lines
