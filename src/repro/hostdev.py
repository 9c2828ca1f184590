"""Pre-jax process bootstrap (deliberately jax-free).

``--xla_force_host_platform_device_count`` is read exactly once, when jax
initializes its backends — so every entry point that wants emulated CPU
devices (the test conftest, ``bench_batch --devices``, ``query_service
--devices``) must inject it into ``XLA_FLAGS`` *before* the first jax
import.  The persistent compilation cache is placed the same way
(``ensure_compile_cache``).  This module centralizes both; importing it
never touches jax.
"""
from __future__ import annotations

import os
import sys

# fixed, in-checkout default for JAX's persistent compilation cache: the
# directory is part of the cache's key, so it must never move between runs
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def ensure_compile_cache() -> str:
    """Keep compiled executables in JAX's persistent compilation cache and
    return its directory: ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
    else the fixed ``DEFAULT_CACHE_DIR``.

    Most of the DP kernels compile in well under a second, below JAX's
    default one-second floor for writing an entry, so the floor is dropped
    to zero (unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` pins
    it).  Call before the first jax import: jax reads both at import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return path


def ensure_host_devices(n: int | None) -> bool:
    """Ask for ``n`` emulated host devices; return True when the request is
    (now or already) expressed in ``XLA_FLAGS``.

    No-op when ``n`` is falsy or 1 (the real-device default), when a count
    is already pinned (an explicit pin wins — if it is smaller than what the
    caller later needs, ``core.shard.take_devices`` raises loudly), or when
    jax is already imported (too late to matter; the caller's
    ``take_devices`` will again fail loudly if devices are missing).
    """
    if not n or n <= 1:
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return True
    if "jax" in sys.modules:
        return False
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()
    return True
