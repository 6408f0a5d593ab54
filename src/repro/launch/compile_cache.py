"""Persistent compilation cache for the entry points.

Called by the launchers, the benchmark harness and ``chip_smoke.py``, never
on ``import repro``: tests and described-topology compiles stay uncached.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: a fixed path, because the path is part of the
# cache key and a directory that moves never hits
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across runs.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and nothing
    is set here; otherwise the cache goes to ``<checkout>/.jax_cache``.
    Call it before the process compiles anything: JAX decides once, at
    the first compile, whether a cache is in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
