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

# JAX's persistent-cache key leaves debug metadata out (the jax name
# stack in ``op_name``, source lines), so a program that differs from a
# cached one only in its ``jax.named_scope``s loads the OLD executable,
# whose ops lack the new scope, and a trace joined with it reads nothing
# under that scope.  The cache therefore lives in a subdirectory named
# after this constant: a PR that adds or renames a scope (list in
# ``dopt.utils.profiling``) bumps it and pays one cold compile.  (Not
# ``jax_compilation_cache_include_metadata_in_key``: source lines are in
# that metadata, so every shifted line would recompile every program.)
PROGRAM_METADATA_VERSION = 8


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere durable and
    return the directory in effect: ``meta-v<PROGRAM_METADATA_VERSION>``
    under ``JAX_COMPILATION_CACHE_DIR`` where that is set (so the cache
    can be placed from outside), else under ``<checkout>/.jax_cache``.
    Call before the first compilation (JAX decides once per process
    whether the cache is in use)."""
    import jax

    base = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    cache_dir = str(Path(base) / f"meta-v{PROGRAM_METADATA_VERSION}")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
