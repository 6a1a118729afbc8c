"""Process set-up shared by the entry points: the persistent compile
cache, and the one line that names the device a run is on.

Importing this module touches no device state; the launchers call
:func:`enable_compile_cache` under their ``__main__`` guard, before
the first compilation.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed path inside the checkout: the cache key includes the
# directory, so a path that moved between processes would never hit
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] \
    / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
    itself, and no other directory is set here).  Otherwise the cache
    lives in ``.jax_cache`` at the root of the checkout, so the next
    process on the same checkout finds what this one compiled.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def device_line() -> str:
    """``platform device_kind xN`` of the visible devices, and the
    compile-cache directory ("off" when the cache is not enabled)."""
    devs = jax.devices()
    cache = jax.config.jax_compilation_cache_dir or "off"
    return (f"device {devs[0].platform} {devs[0].device_kind} "
            f"x{len(devs)}; compile cache {cache}")
