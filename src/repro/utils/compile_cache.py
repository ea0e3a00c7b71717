"""Where JAX's persistent compilation cache lives.

A cache entry is found again only at the same directory, so the directory
is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it,
otherwise ``.jax_cache`` at the root of this checkout.  Entry points (the
serve CLI, the benchmark runner, ``chip_smoke.py``) call
:func:`enable_compile_cache`; importing this module changes nothing.

JAX caches only programs that took at least
``jax_persistent_cache_min_compile_time_secs`` (1 s by default) to compile.
The executor's chunk programs mostly compile faster than that, but a cold
query compiles tens of them, so every program is cached.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    :data:`DEFAULT_DIR`."""
    path = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
