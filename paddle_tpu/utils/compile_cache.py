"""Where JAX's persistent compilation cache lives.

A cold compile of a whole train step or of a serving engine's
executables takes tens of seconds on the chip, so an entry point that
runs there calls `place_compile_cache()` first thing. Nothing calls it
at import time: the tests' compiles for a described (not attached) chip
must not inherit a cache they could write to but never read back.
"""
from __future__ import annotations

import os

import jax

# the cache directory is part of the cache's key, so the default is a
# fixed path inside the checkout — never a temporary or per-process one
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it by itself and
    nothing is set here; otherwise the cache goes to
    `<checkout>/.jax_cache`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
