"""Persistent XLA compilation cache for the repo's JAX entry points.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``) calls ``enable_compile_cache`` before it compiles. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives at ``.jax_cache/`` in the checkout
root: a fixed path, since the path is part of what a later run must find
again. Tests do not call it; the launcher tests that start the entry
points turn the cache off through ``JAX_ENABLE_COMPILATION_CACHE``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
