"""Where the chip entry points keep JAX's persistent compilation cache."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture()
def jax_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_fixed_path_inside_the_checkout(jax_cache_config, monkeypatch,
                                        tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable(str(tmp_path)) == str(tmp_path / ".jax_cache")
    assert compile_cache.enable(str(tmp_path)) == str(tmp_path / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_environment_places_the_cache(jax_cache_config, monkeypatch,
                                      tmp_path):
    outside = str(tmp_path / "elsewhere")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    jax.config.update("jax_compilation_cache_dir", outside)
    assert compile_cache.enable(str(tmp_path / "checkout")) == outside
    assert jax.config.jax_compilation_cache_dir == outside
