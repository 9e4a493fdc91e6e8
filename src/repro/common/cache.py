"""Where the program keeps what it caches between runs: one `.cache/`
directory at the root of the checkout (git-ignored), at a fixed path.

A fixed path matters: JAX's persistent compilation cache keys nothing on the
directory, but a directory that moves between runs (a temporary name, a
process id) never hits.

  * `enable_compile_cache()` — JAX's persistent compilation cache. Where
    `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it and this sets nothing;
    otherwise the cache goes to `.cache/jax`. Called by the launchers and
    `chip_smoke.py` before their first compile.
"""
from __future__ import annotations

import os

#: the checkout root: src/repro/common/cache.py -> three levels up from src
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
