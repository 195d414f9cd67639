"""The persistent compilation cache sits at one fixed path."""
import jax

from repro.launch import cache


def _cache_dir():
    return jax.config.jax_compilation_cache_dir


def test_env_dir_is_left_alone(monkeypatch):
    prev = _cache_dir()
    monkeypatch.setenv(cache.CACHE_ENV, "/elsewhere/jax-cache")
    try:
        assert cache.use_compile_cache() == "/elsewhere/jax-cache"
        assert _cache_dir() == prev        # nothing set in code
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_default_dir_is_fixed_in_checkout(monkeypatch):
    prev = _cache_dir()
    monkeypatch.delenv(cache.CACHE_ENV, raising=False)
    try:
        first = cache.use_compile_cache()
        assert cache.use_compile_cache() == first
        assert _cache_dir() == first
        assert first == str(cache.REPO_CACHE_DIR)
        assert cache.REPO_CACHE_DIR.name == ".jax_cache"
        assert (cache.REPO_CACHE_DIR.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
