"""JAX's persistent compilation cache, at one fixed place per checkout.

``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
itself; no other directory is set in code). Otherwise the cache lives in
``<repo>/.jax_cache`` (ignored by git): a fixed path, because the path is
part of each entry's key, so a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
