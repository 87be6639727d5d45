"""The persistent compilation cache helper (launch/compile_cache.py)."""
import os

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

_OPTIONS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_compilation_cache_include_metadata_in_key")


@pytest.fixture
def jax_cache_config():
    """Restore JAX's cache options (and its lazily opened cache) after a
    test that calls the helper, so no other test compiles into it."""
    from jax.experimental.compilation_cache import compilation_cache
    saved = {k: getattr(jax.config, k) for k in _OPTIONS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_default_dir_is_fixed_in_repo(monkeypatch, jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == REPO_CACHE_DIR == jax.config.jax_compilation_cache_dir
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_enable_compilation_cache


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, jax_cache_config):
    # JAX reads JAX_COMPILATION_CACHE_DIR itself; the helper sets no
    # directory of its own then
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_name_scopes_are_part_of_the_key(monkeypatch, tmp_path,
                                         jax_cache_config):
    """Two programs that differ only in their name scopes get entries of
    their own, so one loaded from the cache keeps its own scopes (which a
    profile attributes device time by)."""
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    def program(scope):
        def step(x):
            with jax.named_scope(scope):
                return jnp.tanh(x) * 2
        return jax.jit(step)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    enable_compile_cache()
    compilation_cache.reset_cache()
    x = jnp.ones(4)
    program("attn")(x)
    program("mlp")(x)
    entries = [f for f in os.listdir(tmp_path) if f.startswith("jit_step")]
    assert len(entries) == 2
