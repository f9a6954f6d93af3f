"""Where entry points keep JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``scripts/*.py``)
call :func:`enable` once at start-up; library code and tests never do.
If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
and nothing else is set.  Otherwise the cache goes to ``.jax_cache/`` at
the repository root: a fixed path, so that the next run finds it (a
directory named after a temp file, pid or time is never found again).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
