"""Where the entry points put JAX's persistent compilation cache."""

import os

import jax

from repro.launch import compile_cache


def _recording(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: calls.append((name, value)))
    return calls


def test_environment_directory_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _recording(monkeypatch)
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_is_the_checkout_root(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _recording(monkeypatch)
    path = compile_cache.use_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert path == os.path.join(compile_cache.ROOT, ".jax_cache")
    # the root is the checkout: it holds the sources and ignores the cache
    assert os.path.isfile(os.path.join(compile_cache.ROOT, "src", "repro", "launch",
                                       "compile_cache.py"))
    with open(os.path.join(compile_cache.ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
