"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]

# repository checkout root: src/repro/launch/compile_cache.py → 3 levels up
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and nothing else is set here. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path, so later runs from the same
    checkout find what earlier ones compiled. Call it from an entry
    point's ``main()``; importing this module changes nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
