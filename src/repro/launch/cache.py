"""Persistent compilation cache for the program's entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py``,
``benchmarks/run.py`` and ``repro.launch.serve`` / ``train`` before their
first compile — never at import, so library users and the tests keep
jax's defaults.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it
itself and nothing is set here.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the repository root: the directory is part of the
cache key, so a path that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
