"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so the directory must not move
between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names
(JAX reads that variable itself, and nothing here overrides it) or one
fixed, git-ignored directory inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
