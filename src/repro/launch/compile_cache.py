"""JAX persistent compilation cache, placed from outside the program.

Entry points that compile many programs (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before their
first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, decides where the
cache lives and JAX reads it itself; otherwise the cache goes to one fixed
directory inside the checkout.  The path is part of what makes an entry hit,
so it is never built from a temp name, a pid or the time.  Importing this
module (or any of ``repro``) leaves the JAX config untouched.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every compile is cached, however short: the kernels and sweeps of the
    tuner compile in well under JAX's one-second default threshold, and a
    cold call would otherwise recompile them all.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
