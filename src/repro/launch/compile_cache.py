"""One persistent XLA compilation cache for every entry point.

A cold compile of a full-width train step takes minutes on the chip;
a cache makes the second run of a command reuse the first run's
programs. The cache directory is part of each entry's key, so it must
not move between runs: it is either the directory the environment
names (``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself) or the
fixed ``.jax_cache`` directory at the root of this checkout — never a
temporary, pid- or time-derived path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
REPO_CACHE = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory. Call before
    the first compile."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
