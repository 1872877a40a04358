"""JAX's persistent compilation cache, shared by the dense engine and the
sweep runner.

Paper sweeps re-launch the same (scheme, topology, shape) programs in every
process — several seconds of XLA compile each — so from the second process
on the compiles are disk hits.  The directory is ``JAX_COMPILATION_CACHE_DIR``
when set (or an earlier ``jax.config`` update); otherwise a fixed directory
inside the checkout, ``DEFAULT_COMPILE_CACHE``.
"""
from __future__ import annotations

import os
import threading

import jax
from jax.experimental.compilation_cache import compilation_cache

#: the compile cache's home when JAX_COMPILATION_CACHE_DIR is not set: a
#: fixed directory inside the checkout, shared by all of its processes
DEFAULT_COMPILE_CACHE = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"))

_ENABLED = False
_LOCK = threading.Lock()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return the directory
    in use.  Idempotent and thread-safe (``sweep.run_jobs`` workers race
    to compile); called lazily by the dense engine and the sweep runner."""
    global _ENABLED
    with _LOCK:
        if not _ENABLED:
            if not jax.config.jax_compilation_cache_dir:
                jax.config.update("jax_compilation_cache_dir",
                                  DEFAULT_COMPILE_CACHE)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
            # key on the HLO metadata too: the step's named scopes live
            # there (``obs.scopes``), and a cache shared with a build from
            # before them holds the same program keyed alike, whose
            # ``op_name``s ``sweep.op_phases`` would then read.  The
            # metadata holds source lines and call sites, so a program
            # traced from a new call site or after an edit compiles once
            # more.
            jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
            # the cache module latches "no dir configured" on the first
            # compile of the process (e.g. a jnp op at import time) and
            # never re-reads the config — reset so the dir takes effect
            compilation_cache.reset_cache()
            _ENABLED = True
    return jax.config.jax_compilation_cache_dir
