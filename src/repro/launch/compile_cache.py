"""JAX's persistent compilation cache, placed from outside the program.

CAFL-L compiles a new executable for every knob shape, so a cold run
spends much of its time compiling. Every entry point calls
``setup_compile_cache()`` first thing inside ``main()`` (never at
import): a run then reuses what an earlier run in the same cache
directory compiled.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise the fixed ``<checkout>/.jax_cache`` (listed in .gitignore).
The path is part of the cache key, so it never depends on time, pid
or temporary names.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: src/repro/launch/ -> three levels up
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def setup_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable: the per-knob-shape programs and the wire
    # kernels each compile in well under JAX's 1 s default threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
