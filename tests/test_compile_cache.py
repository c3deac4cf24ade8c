"""Where the entry points put JAX's persistent compilation cache.

Only the directory rule is exercised: tests never turn the cache on.
"""
from pathlib import Path

from repro.utils import compile_cache

REPO = Path(__file__).resolve().parent.parent


def test_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert Path(compile_cache.compile_cache_dir()) == REPO / ".jax_cache"


def test_cache_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
