"""JAX's persistent compilation cache, kept where the next run finds it.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``)
call :func:`enable` once before they compile anything; importing
``repro`` never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable(checkout) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and no directory is set here. Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path (never a temporary, pid- or
    time-based one), so every run from that checkout hits the same
    entries.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
