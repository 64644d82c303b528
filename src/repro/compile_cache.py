"""Persistent XLA compilation cache for the repo's entry points.

Every entry point (chip_smoke.py, benchmarks/run.py, examples/) calls
`enable()` before its first compile.  The cache path is part of each
entry's key, so it is a fixed directory: `JAX_COMPILATION_CACHE_DIR` when
it is set (JAX reads that variable itself, so nothing is set here), else
`<checkout>/.jax_cache`, which .gitignore lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> Path:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return CHECKOUT_CACHE
