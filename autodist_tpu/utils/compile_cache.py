"""Placement and keying of JAX's persistent compilation cache.

The cache directory is part of the cache key's surroundings: a directory
that moves never hits.  So there are exactly two places it can be — where
``JAX_COMPILATION_CACHE_DIR`` says (JAX reads the variable itself, no
directory is set here), or one fixed path inside the checkout.

The key covers the program's metadata.  By default JAX strips names and
source lines before it hashes a program, so an executable that another
commit compiled is loaded for a step that differs from it in
``jax.named_scope`` names only, and a profile then shows that commit's
``op_name``s: the ``ad.*`` scopes (PERF.md section 3) would be missing
from, or stale in, every metric read from them.  With the metadata in the
key a cached step is the step this source describes.  Source paths are
recorded relative to the checkout, so that two checkouts of one commit
still share their entries.
"""
import os
import re

import jax

CHECKOUT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
IN_CHECKOUT_CACHE_DIR = os.path.join(CHECKOUT_DIR, ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure a persistent compile cache is configured; returns its
    directory.  Called from ``AutoDist.__init__``, so every entry point
    shares one cache."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT_DIR + os.sep))
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", IN_CHECKOUT_CACHE_DIR)
    return IN_CHECKOUT_CACHE_DIR
