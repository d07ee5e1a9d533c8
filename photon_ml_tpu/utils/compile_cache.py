"""Persistent XLA compilation cache.

The reference has no analog (JVM bytecode is its "compiled artifact"); on
TPU the expensive artifact is the XLA executable — seconds to minutes per
program, and a GAME fit builds one per bucket shape. JAX's persistent
compilation cache serializes executables keyed by HLO hash, so every
process after the first (re-runs of a driver, the benchmark, CI shards)
loads them instead of compiling.

One rule for where it lives: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself; no directory is set
in code), otherwise ``.jax_cache`` beside the package. The path is part
of the cache key, so it is always a fixed location. Call
:func:`enable_compilation_cache` before the first ``jit`` execution.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("photon_ml_tpu.utils")

_DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache``
    beside the package. Either way every program is cached (no
    compile-time or size floor). A directory that cannot be created is
    logged at warning level — the run carries on uncached, visibly."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        logger.warning("compilation cache directory %s cannot be created "
                       "(%s): programs will compile on every start", path, e)
    # Cache everything: even sub-second compiles add up across the many
    # per-bucket-shape programs a GAME fit builds.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
