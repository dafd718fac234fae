"""Where JAX's persistent compilation cache lives.

One rule for every process of the program: where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and
nothing here touches it; where it is not, the cache is
``<checkout>/.jax_cache`` — a fixed path, because the path is part of
the cache key and a directory that moves never hits. The choice is
exported through the environment, so worker processes (Serve replicas,
train workers) inherit it from the process that spawned them.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# JAX caches only programs that took a second to compile. On the chip
# every eager one-op program costs about half a second and falls under
# that: a replica's init is 22 of them. Cache them all unless told.
MIN_SECS_VAR = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Resolve the cache directory, export it, and return it. Imports
    nothing: JAX reads the variable itself when it is imported later; a
    JAX that is already imported is told through its config."""
    path = os.environ.setdefault(ENV_VAR, DEFAULT_DIR)
    min_secs = float(os.environ.setdefault(MIN_SECS_VAR, "0"))
    jax = sys.modules.get("jax")
    if jax is not None:
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
    return path
