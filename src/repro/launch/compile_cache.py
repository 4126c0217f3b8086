"""JAX's persistent compilation cache, at one place per checkout.

A chip run starts with no compiled code, and a cold run can spend much
of its time compiling; processes of one run (a fleet's replicas, the
phases of the smoke script) share what any of them compiled.  The cache
key includes the directory, so the directory must not move between
runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: fixed, inside the checkout (and listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Where
    ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
