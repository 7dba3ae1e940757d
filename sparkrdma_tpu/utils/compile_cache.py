"""JAX's persistent compilation cache, placeable from outside.

Every entry point that compiles at real size (``chip_smoke.py``,
``bench.py``, ``benchmarks/run_workloads.py``) calls
:func:`enable_compile_cache` once, before its first compile, so a run
that repeats the shapes of an earlier one loads its executables
instead of compiling them again.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — gitignored; a fixed path, because the
# directory is part of what a later run must find again
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile and return its
    directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no directory in code; otherwise the cache lives at
    :data:`DEFAULT_DIR`."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
