"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the same cache path, so the
path must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it, else the fixed ``.jax_cache/`` at the repository
root (listed in ``.gitignore``). Entry scripts call :func:`enable` once,
before their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: src/repro/compile_cache.py -> the repository root's .jax_cache/
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it as the
    cache directory, so nothing else is set; otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
