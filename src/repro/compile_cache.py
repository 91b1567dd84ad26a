"""Where JAX keeps its persistent compilation cache, and what it keeps.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
``enable_compile_cache()`` before their first compile; importing ``repro``
never does. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and
nothing here overrides it. Otherwise the cache lives at a fixed path in the
checkout, ``<checkout>/.jax_cache``: the path is part of what a later run
looks up, so it never depends on a temporary name, a pid or the time.

Either way every compiled program is kept. JAX's default skips programs that
compiled in under a second, and an index build requests hundreds of those
(one k-means and one router top-k program per partition shape): on a TPU
v5e they cost a warm 1M-row build 70 s of recompiling.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
