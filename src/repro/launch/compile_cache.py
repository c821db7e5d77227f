"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, :mod:`repro.launch.replay`, the
example scripts) calls :func:`enable_compile_cache` before its first
compile, so the while-loop solves and DiDiC steps compiled by one run are
found again by the next run on the same machine.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it on its own
and nothing is set in code. Otherwise the cache lives at a fixed path
inside the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``).
The path is part of what makes a cache hit possible, so it is never built
from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
