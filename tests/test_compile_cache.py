"""The entry points' persistent compilation cache location."""
import jax

from repro.launch.compile_cache import (
    CHECKOUT, compile_cache_dir, enable_compile_cache,
)


def test_env_var_wins():
    assert compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/srv/cache"}) == "/srv/cache"


def test_fixed_path_in_checkout_otherwise():
    assert (CHECKOUT / "src" / "repro" / "launch").is_dir()
    assert compile_cache_dir({}) == str(CHECKOUT / ".jax_cache")
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == \
        str(CHECKOUT / ".jax_cache")


def test_enable_points_jax_at_it(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            str(CHECKOUT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
