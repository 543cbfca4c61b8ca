"""Process start-up against the JAX back-end: compile cache + device report.

Every entry point that compiles for the chip calls
:func:`configure_compile_cache` before its first jit and
:func:`log_device_info` once after it, so that no run starts from an empty
cache by accident and no number is ever printed without the device it came
from (docs/OPERATIONS.md "Running on the chip").
"""

from __future__ import annotations

import os
from typing import Dict, Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout cache directory (git-ignored). FIXED on purpose: the
#: path is part of the cache key, so a directory under tempfile, a pid or a
#: timestamp never hits.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def cpu_only() -> bool:
    """True when ``JAX_PLATFORMS`` restricts this process to the CPU (the
    sealed chip machine exports ``tpu,cpu``; unset lets JAX take the best
    back-end present)."""
    plats = [
        p.strip()
        for p in os.environ.get("JAX_PLATFORMS", "").split(",")
        if p.strip()
    ]
    return bool(plats) and all(p == "cpu" for p in plats)


def configure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set in code (an operator or the chip tool owns the place).
    Otherwise the cache goes to :data:`REPO_CACHE_DIR` — except in a
    CPU-only process, which gets none (returns None): the cache exists for
    chip compiles, and this jaxlib's CPU client logs a machine-feature
    mismatch error on every cached executable it loads. Child processes
    that compile for a chip call this too and resolve the same directory.
    Wherever a cache is in use its key takes in the program's metadata.
    """
    placed = os.environ.get(CACHE_DIR_ENV)
    if not placed and cpu_only():
        return None
    import jax

    # the default key ignores op names and source lines, so a program read
    # from the cache keeps those it was first compiled with: a capture
    # would show an older build's scopes (utils/profiling.py), or none
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def device_info() -> Dict[str, object]:
    """The device as JAX reports it (initialises the back-end)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def log_device_info() -> Dict[str, object]:
    """Log the device and the cache directory once; returns the device."""
    import jax

    from distributed_ba3c_tpu.utils import logger

    info = device_info()
    logger.info(
        "device: platform=%s kind=%s count=%d (jax %s, compile cache %s)",
        info["platform"], info["kind"], info["count"], jax.__version__,
        os.environ.get(CACHE_DIR_ENV) or jax.config.jax_compilation_cache_dir,
    )
    return info
