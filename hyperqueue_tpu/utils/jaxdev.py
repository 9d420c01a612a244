"""What the solve needs to know about the JAX runtime it runs on.

Two facts, kept out of the models so that every process that is about to
touch JAX for the solve (server start, chip_smoke.py) says them
the same way: where compiled programs are kept between processes, and
which device an array the solve returned actually lives on.
"""

from __future__ import annotations

import os
from pathlib import Path

# One fixed place inside the checkout: the directory is part of the cache
# key, so a path built from a temp dir, a pid or the time would never hit.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Call once, before the process compiles anything.  A directory given
    from outside (JAX_COMPILATION_CACHE_DIR, which jax reads itself) is
    left alone; otherwise the cache lives at COMPILE_CACHE_DIR.  The
    thresholds drop to zero either way: the slicer and scatter programs
    compile in well under jax's default one-second floor and every tick
    shape needs them, so leaving them out would recompile them in every
    process."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def device_block(arr) -> dict:
    """{platform, kind, count} of the devices holding `arr` (a jax Array a
    solve returned) — read from the array, not from what the process was
    asked to use."""
    devices = sorted(arr.devices(), key=lambda d: d.id)
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
