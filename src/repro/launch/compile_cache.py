"""JAX's persistent compilation cache, for the repository's entry points.

``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` before they compile
anything; tests and library imports never do. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there and no other
directory is set. Otherwise the cache lives at ``.jax_cache/`` in the
checkout: a fixed path, since the path is part of what a later run must find.

The helper works through the environment, so a parent that never imports JAX
(``benchmarks/run.py``) hands the same cache to its child processes; in a
process that has already imported JAX it also updates the live config.
"""
from __future__ import annotations

import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")

# cache every program, however quick its compile: a rerun compiles nothing
_THRESHOLDS = {"jax_persistent_cache_min_compile_time_secs": 0.0,
               "jax_persistent_cache_min_entry_size_bytes": 0}


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    for name, value in _THRESHOLDS.items():
        os.environ.setdefault(name.upper(), str(value))
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
        for name, value in _THRESHOLDS.items():
            jax.config.update(name, type(value)(os.environ[name.upper()]))
    return path
