"""Process set-up shared by the entry points that drive a device: JAX's
persistent compile cache and the serving engine's device memory budget."""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR_ENV", "DEFAULT_CACHE_DIR", "ACTIVATION_MARGIN_BYTES",
           "enable_compile_cache", "device_hbm_budget"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout: the directory is part of the cache key,
# so a path that moved between runs would never hit
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# Device memory the serving budget leaves to what the engine's ledger does
# not count: the compiled programs' temporaries (a packed tick's activations
# and MLP intermediates, at yi-6b widths and a 1024-token stream about
# 0.3 GB) and the runtime's own reservations.
ACTIVATION_MARGIN_BYTES = 1 << 30


def enable_compile_cache() -> str:
    """Turn JAX's persistent compile cache on and return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, that
    directory is used and nothing is set here.  Otherwise the cache is
    ``.jax_cache/`` at the root of the checkout."""
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_hbm_budget(device=None) -> int | None:
    """The serving HBM budget of one device: its allocator's
    ``bytes_limit`` less :data:`ACTIVATION_MARGIN_BYTES`.  None when the
    device reports no limit (the CPU backend), so no size is ever
    assumed."""
    device = device if device is not None else jax.devices()[0]
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - ACTIVATION_MARGIN_BYTES
