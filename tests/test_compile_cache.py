"""The persistent compilation cache: its location and its key (repro.common.cache)."""
from pathlib import Path

import jax
import pytest

from repro.common import cache


@pytest.fixture(autouse=True)
def restore_key_hook(monkeypatch):
    """enable_compile_cache() sets JAX's cache-key hook: undo it after each test."""
    from jax._src import cache_key
    monkeypatch.setattr(cache_key, "custom_hook", cache_key.custom_hook)


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



def test_entries_are_keyed_on_the_package_sources(monkeypatch):
    """JAX's key leaves out op metadata (the scopes a trace reports): the
    cache keys on a digest of the package's sources as well, so a program
    compiled from other code is never loaded in its place."""
    import numpy as np
    from jax._src import cache_key, compiler

    monkeypatch.setattr(cache_key, "custom_hook", lambda: "")
    monkeypatch.setattr(jax.config, "update", lambda *a: None)
    module = jax.jit(lambda x: x * 2).lower(np.float32(1)).compiler_ir("stablehlo")
    options = compiler.get_compile_options(num_replicas=1, num_partitions=1)
    devices = np.array(jax.devices()[:1])

    def key():
        return cache_key.get(module, devices, options, jax.devices()[0].client)

    plain = key()
    assert cache.enable_compile_cache()
    assert key() != plain and key() == key()
    assert cache_key.custom_hook() == cache.source_digest()
    assert len(cache.source_digest()) == 64
