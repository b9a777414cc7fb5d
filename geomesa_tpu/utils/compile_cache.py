"""JAX's persistent compilation cache, placed from outside or at one fixed
path in the checkout.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
names another directory. Unset: ``<repo>/.jax_cache`` (gitignored) — a
fixed path, because the directory is part of what a later run must find
again; a temp, pid- or time-derived path would never hit.

Called by the entry points that own a process (``chip_smoke.py``,
``bench.py``, ``geomesa-tpu serve``), never at import: a test process
that compiles for a described chip must not write into it.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir = env_dir
    else:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # every step program is worth keeping: the store's kernels compile in
    # well under JAX's 1 s default threshold, and a cold call pays for all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
