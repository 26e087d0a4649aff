"""JAX's persistent compilation cache for the entry points that run on
the chip (``chip_smoke.py``, ``benchmarks/run.py``); tests never use it.

The cache directory is part of every entry's key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself and nothing else is set here), otherwise
the fixed, git-ignored ``.jax_cache/`` at the repository root.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory. Call before the first compile."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
