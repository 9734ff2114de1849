"""Persistent XLA compilation cache for the entry points.

Entry points (`chip_smoke.py`, ``python -m repro.api.http``, the benchmark
and example scripts) call `enable()` from their `main()`, never at import.
Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already keeps its cache
there and nothing else is configured.  Otherwise the cache goes to
`.jax_cache/` at the root of the checkout: one fixed path, so a later run
finds what an earlier one compiled (never a pid, a time or a temp name:
the path is part of the cache's key).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
