"""Where JAX's persistent compilation cache lives.

`JAX_COMPILATION_CACHE_DIR`, when set, wins: JAX reads it itself, so the
cache stays where the environment put it and no other directory is set.
Otherwise the cache goes to the fixed path `<checkout>/.jax_cache` (listed
in `.gitignore`).  The path is part of the cache key, so it is never built
from a temporary name, a pid or the time: a directory that moves never hits.
Either way, programs that take half a second or more to compile are kept
(JAX's default threshold is one second; the quick benchmarks and the
serving warm-up compile many programs just under it), together with XLA's
own auxiliary caches.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
