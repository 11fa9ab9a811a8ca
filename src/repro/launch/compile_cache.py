"""JAX's persistent compilation cache, configured in one place.

Every command-line entry point (``chip_smoke.py``, ``repro.launch.serve``,
the benchmarks and the examples) calls :func:`init_compile_cache` before
its first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, that
directory is used and no other is set here.  Otherwise the cache lives at
one fixed path inside the checkout (``.jax-cache/``, ignored by git): the
path is part of what a later run must find again, so it never depends on
a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax-cache`` (this file is ``src/repro/launch/...``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax-cache"


def init_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory used."""
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
