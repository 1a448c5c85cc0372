"""Persistent XLA compilation cache for the launchers' entry points.

A whole train step of a 24-layer model takes about a minute to compile
for a TPU; the cache lets a second run in the same checkout skip that.
The cache directory is part of every entry's key, so it must not move
between runs: it is ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX
reads the variable itself, and nothing here overrides it), and otherwise
the fixed ``<checkout>/.jax_cache``.

Only entry points call :func:`enable_compile_cache` — never an import or a
test — so library users and the test suite keep JAX's own defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Entries are keyed on the programs' metadata too.  The named scopes by
    which a profile's device time is read per layer live only there, so a
    key without it hands a program the cached executable of another
    revision that differs from it in op names alone."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
