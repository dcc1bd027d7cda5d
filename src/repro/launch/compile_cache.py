"""JAX persistent compilation cache for the entry points.

``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile, so a second run
of the same programs reads its executables back instead of compiling
them again. The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is
set (an outside placement wins, and no other directory is set), and the
fixed path ``<checkout>/.jax_cache`` otherwise. The path is part of the
cache's key, so it is never built from a temporary name, a pid or the
time: a directory that moves never hits.

A process whose engines run tensor-parallel over device groups keeps
the cache off. On a TPU v5e 2x2 host the prefill TP2 -> decode TP2 plane
halted the chip ("Core halted unexpectedly ... enhanced-barrier") in
each of three runs with the cache on, and ran clean with it off.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache(engine_group_size: int = 1) -> Optional[str]:
    """Point JAX's persistent compilation cache at its directory and
    cache every program, however fast it compiled (the default skips
    programs under one second, which on a cold chip run are most of the
    engine's). Returns the directory, or None when the largest engine
    device group exceeds one device and the cache is turned off."""
    import jax
    if engine_group_size > 1:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
