"""The persistent compilation cache location (repro.common.cache)."""
from pathlib import Path

import jax
import pytest

from repro.common import cache


def test_env_dir_wins_and_is_left_to_jax(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(cache.CACHE_ENV, str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)
    assert cache.enable_compile_cache() == str(tmp_path)
    assert calls == []          # JAX reads the variable itself


@pytest.mark.parametrize("unset", ["missing", "empty"])
def test_fixed_checkout_dir_without_env(monkeypatch, unset):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    if unset == "missing":
        monkeypatch.delenv(cache.CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(cache.CACHE_ENV, "")
    first, again = cache.compile_cache_dir(), cache.enable_compile_cache()
    assert first == again == cache.compile_cache_dir()
    root = Path(__file__).resolve().parents[1]
    assert Path(first) == root / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", first)]
