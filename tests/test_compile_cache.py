"""The launchers' persistent-compilation-cache helper: where the cache
lands, without ever turning it on in the test process."""

from pathlib import Path

import jax

from repro.launch import compile_cache

# a profile read by named scope needs executables compiled from this source
METADATA_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


def _capture_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_wins_and_nothing_is_overridden(monkeypatch, tmp_path):
    calls = _capture_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == [METADATA_IN_KEY]


def test_default_is_the_fixed_checkout_path(monkeypatch):
    calls = _capture_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = compile_cache.enable_compile_cache()
    checkout = Path(__file__).resolve().parent.parent
    assert where == str(checkout / ".jax_cache")
    assert calls == [METADATA_IN_KEY, ("jax_compilation_cache_dir", where)]
    # the same path on every call: no pid, time or temporary name in it
    assert compile_cache.enable_compile_cache() == where
