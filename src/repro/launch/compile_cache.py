"""JAX's persistent compilation cache for the launchers and the chip smoke run.

The cache key includes the directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it,
otherwise one fixed directory inside the checkout (listed in .gitignore).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`;
    returns the directory."""
    d = cache_dir()
    jax.config.update("jax_compilation_cache_dir", d)
    return d
