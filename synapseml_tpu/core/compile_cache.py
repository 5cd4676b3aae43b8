"""Persistent XLA executable cache (jax compilation cache) enablement.

One shared entry point for bench.py, the tools, the serving warmup and the
test harness. Large compiles (the fused training scan is 40-110 s for the
chip) are paid once per configuration, not once per process.

Where the cache lives is decided outside the program when
``JAX_COMPILATION_CACHE_DIR`` is set: jax reads that variable into
``jax_compilation_cache_dir`` itself and no directory is set in code, so a
caller (the chip tool, a deployment) can carry the cache from run to run.
Otherwise it is the fixed ``<checkout>/.jax_cache`` — never a temporary or
per-process path, because the path is part of what makes a later run find
the entries.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Enable the persistent cache; returns the directory in force."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax.config.jax_compilation_cache_dir
