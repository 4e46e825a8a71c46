"""JAX's persistent compilation cache for programs that run on the chip.

Called by the launchers and `chip_smoke.py`, never at `import repro`: tests
compile for a described (not attached) chip and must not write a cache.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
else is set here.  Otherwise the cache lives at `<checkout>/.jax_cache`, a
fixed path: the path is part of the cache key, so a directory named after a
temp dir, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
