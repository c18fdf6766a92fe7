"""JAX process set-up shared by every entry point.

Device selection is JAX's own: `JAX_PLATFORMS=cpu` runs the tests and
dry-runs on the CPU, and with the variable unset JAX takes the TPU. The one
thing entry points share is where compiled executables persist.
"""

from __future__ import annotations

import os

#: `<checkout>/.jax_cache` — derived from this package's own location, so it
#: is the same path on every start (the directory is part of the cache key:
#: a cache that moves never hits)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Give JAX's persistent compilation cache a home, so a cold start does
    not recompile the ingest ladder, the roll and their warm-up twins.
    Call before first backend use; returns the directory in effect.

    With `JAX_COMPILATION_CACHE_DIR` in the environment JAX reads it itself
    and this sets nothing; otherwise the cache lives at `DEFAULT_CACHE_DIR`.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
