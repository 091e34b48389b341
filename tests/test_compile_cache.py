"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_dir_from_outside_is_left_to_jax(monkeypatch,
                                               restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    checkout = compile_cache.CHECKOUT
    assert path == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the checkout is the repository root, and the path is ignored by git
    assert (checkout / "src" / "repro" / "launch" / "compile_cache.py"
            ).is_file()
    assert ".jax_cache/" in (checkout / ".gitignore").read_text().split()
    # no temp name, pid or time in it: a second call picks the same path
    assert compile_cache.use_compile_cache() == path
