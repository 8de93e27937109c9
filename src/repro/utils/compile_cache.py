"""Where JAX keeps its persistent compilation cache.

The cache's key includes its directory, so a directory that moves never
hits. ``JAX_COMPILATION_CACHE_DIR`` names it from outside; otherwise it is
one fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored),
never built from a temporary name, a process id or the time. The entry
points (``python -m repro.opt``, ``python -m repro.serve``,
``chip_smoke.py``) call ``enable_compile_cache`` before their first
compile.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/utils/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``DEFAULT_DIR``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    import jax
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["DEFAULT_DIR", "compile_cache_dir", "enable_compile_cache"]
