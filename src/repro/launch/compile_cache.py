"""Persistent XLA compilation cache for the entry points.

A full-width DiT-XL/2 sampler takes minutes to compile, and a cold run pays
that for the drift sampler and again for its clean-reference twin. The
entry points (``launch/serve.py``, ``examples/drift_serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`enable_compile_cache`
once at start so later runs in the same checkout find the executables.
Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# A fixed path: the cache directory is part of the cache key, so a
# directory that moves between runs never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    the cache stays there. Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
