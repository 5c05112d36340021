"""JAX's persistent compilation cache for the entry points.

The entry points (``chip_smoke.py``, :func:`repro.launch.serve.main`,
:func:`repro.launch.train.main`) call :func:`enable_compile_cache`
before their first compile; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

#: ``.jax_cache/`` at the repository root (git-ignored).  A fixed path:
#: the cache only hits for a directory that stays where it was.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is changed.  Otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
