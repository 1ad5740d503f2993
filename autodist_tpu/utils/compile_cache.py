"""Placement of JAX's persistent compilation cache.

The cache directory is part of the cache key's surroundings: a directory
that moves never hits.  So there are exactly two places it can be — where
``JAX_COMPILATION_CACHE_DIR`` says (JAX reads the variable itself, nothing
is set here), or one fixed path inside the checkout.
"""
import os

import jax

IN_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure a persistent compile cache is configured; returns its
    directory.  Called from ``AutoDist.__init__``, so every entry point
    shares one cache."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", IN_CHECKOUT_CACHE_DIR)
    return IN_CHECKOUT_CACHE_DIR
