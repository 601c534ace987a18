"""Persistent JAX compilation cache for the repository's entry points.

`chip_smoke.py`, `benchmarks/fleet.py` and the `examples/` scripts call
`enable()` before their first compile, so processes that compile the
same programs in one checkout share their compiled code.
"""
from __future__ import annotations

import os

import jax

# fixed, inside the checkout and git-ignored: a path made from a pid, the
# time or a temp name would start empty in every process and never hit
CHECKOUT_CACHE = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to
    `<checkout>/.jax_cache`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
