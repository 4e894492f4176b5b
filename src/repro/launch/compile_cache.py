"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and
nowhere else.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout: the directory is part of what makes a
later run find an entry, so it is never built from a temporary name, a
pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> Path:
    """The directory the persistent compilation cache uses."""
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR)


def enable_compile_cache() -> Path:
    """Turn the persistent cache on for every compile of this process
    (call before the first compile); returns its directory.  Compiles of
    any duration are kept: each PE compiles its ifunc slices in well
    under JAX's default one-second floor."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
