"""Process set-up shared by every entry point.

Device selection is JAX's own: `JAX_PLATFORMS=cpu` runs the tests and
dry-runs on the CPU, and with the variable unset JAX takes the TPU. What
entry points share is where compiled executables persist, and how the C
heap keeps the drain's per-eviction arrays.
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


#: glibc `mallopt` parameters (malloc.h)
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3
#: the largest M_MMAP_THRESHOLD glibc takes on 64-bit (half a thread arena's
#: 64 MiB heap), which is also the ceiling its dynamic threshold rises to
MMAP_THRESHOLD = 32 << 20
#: the main arena returns a free top to the kernel only past this
TRIM_THRESHOLD = 256 << 20
#: more than a thread arena's 64 MiB heap: glibc unmaps a thread arena's
#: extra heap the moment it empties, whatever the trim threshold, unless
#: the top pad it must keep is larger than the heap
TOP_PAD = 128 << 20


def pin_malloc_thresholds() -> bool:
    """Keep freed heap memory for reuse instead of handing it back to the
    kernel. Each columnar drain allocates its arrays afresh (the events
    alone are 144 bytes a record: 14 MB for a 100,000-record eviction) and
    frees the previous eviction's once the exporter is done with it. Under
    glibc's defaults a process can fall into a mode where every other
    eviction's events land alone in a thread arena's extra heap, which is
    unmapped when they are freed and mapped afresh for the next: thousands
    of page faults a drain. Where page faults are dear (a sandboxed kernel)
    that is a drain a fifth slower, in some processes and not in others.
    Fixed thresholds and a top pad larger than a heap end the mode: arrays
    up to `MMAP_THRESHOLD` come from the heap, and no heap is unmapped or
    shrunk. Untouched pages of a kept heap cost no memory.

    glibc only, process-wide, idempotent. An operator's own malloc
    settings in the environment win: then this sets nothing. Returns
    whether the settings are in effect."""
    if (os.environ.get("MALLOC_TRIM_THRESHOLD_")
            or os.environ.get("MALLOC_MMAP_THRESHOLD_")
            or os.environ.get("MALLOC_TOP_PAD_")
            or "malloc" in os.environ.get("GLIBC_TUNABLES", "")):
        return False
    try:
        import ctypes

        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        return False
    # the mmap threshold first: setting any of the three stops glibc moving
    # the thresholds, and without it every array over 128 KiB would be mmap'd
    return bool(mallopt(_M_MMAP_THRESHOLD, MMAP_THRESHOLD)
                and mallopt(_M_TRIM_THRESHOLD, TRIM_THRESHOLD)
                and mallopt(_M_TOP_PAD, TOP_PAD))
