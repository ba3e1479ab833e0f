"""Where JAX's persistent compilation cache lives for this repo's entry points.

Called by ``chip_smoke.py`` and ``benchmarks/run.py`` before their first
compile, never at import of ``repro``.  The cache directory is part of
every entry's key, so it is a fixed path — never a temporary, per-process
or time-stamped one, which would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore).
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``REPO_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
