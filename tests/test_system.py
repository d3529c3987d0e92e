"""The launchers' compile cache."""


def test_compile_cache_dir_env_or_checkout(monkeypatch):
    """The entry points' compile cache: `JAX_COMPILATION_CACHE_DIR` when set
    (JAX reads it; nothing else is configured), else the fixed `.jax_cache/`
    at the checkout root. The config update is captured, not applied, so
    this test leaves the cache off."""
    from pathlib import Path

    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
    assert compile_cache.enable_compile_cache() == "/some/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.enable_compile_cache() == str(root / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", str(root / ".jax_cache"))]
