"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the directory it was written
to, so the path must be the same in every run: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself, and nothing
is set here), else ``<repo>/.jax_cache`` — never a path derived from a
temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
