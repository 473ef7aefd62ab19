"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m benchmarks.run`` call :func:`enable_compile_cache` once at
start-up, so the stacked evaluator scan and the model's interval program
compile once per cache directory instead of once per process.  Library
modules never call it: importing the package changes no JAX setting.
"""
from __future__ import annotations

import os
import pathlib

#: Used when ``JAX_COMPILATION_CACHE_DIR`` is unset.  A fixed path inside
#: the checkout (gitignored): the directory is part of the cache key, so a
#: path built from a temp dir, a pid or a time would never hit.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
