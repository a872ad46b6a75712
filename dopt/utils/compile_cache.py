"""Where XLA's persistent compilation cache lives.

Model1's round program compiles in tens of seconds and the CNN presets
in minutes; every entry point (``dopt.run``, ``dopt.serve``,
``bench.py``, ``chip_smoke.py``, the scripts) calls
``enable_compile_cache()`` before its first compilation so a second run
of the same command starts from cache.
"""

from __future__ import annotations

import os
from pathlib import Path

# Fixed, inside the checkout and git-ignored: a cache directory that
# moves (a temporary name, a pid, a timestamp) never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere durable and
    return the directory in effect.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already honours it — nothing
    is set in code, so the cache can be placed from outside.  Unset:
    ``<checkout>/.jax_cache``.  Call before the first compilation (JAX
    decides once per process whether the cache is in use)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
