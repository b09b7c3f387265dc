"""Placement of JAX's persistent compilation cache (repro.launch.compile_cache)."""

import pytest

import jax

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in old.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_dir_from_env_or_fixed_checkout_path(
    env_set, monkeypatch, tmp_path, restore_cache_config
):
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.DEFAULT_DIR)
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_default_cache_dir_is_fixed_and_ignored():
    """The default sits in the checkout at a path a later process finds
    again, and git does not track what is cached there."""
    root = compile_cache.DEFAULT_DIR.parent
    assert (root / "src" / "repro" / "launch" / "compile_cache.py").is_file()
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
