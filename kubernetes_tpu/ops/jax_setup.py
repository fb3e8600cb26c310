"""Process-wide JAX configuration for the solver path.

Enables the persistent compilation cache so a fresh process (every
benchmark run; every scheduler restart) reuses XLA binaries instead of
re-paying the device compile for each solver shape. Must be imported
before the first jit compilation — ``kubernetes_tpu.ops`` imports it
first.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
directory is set here. Otherwise the cache lives at the fixed
``<repo>/.jax_cache``: the path is part of the cache key, so it must
not move between runs.
"""

from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def configure() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


configure()
