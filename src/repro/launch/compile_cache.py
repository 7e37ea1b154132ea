"""JAX's persistent compilation cache, in one place for every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
itself; nothing else is configured).  Otherwise the cache lives at one
fixed path inside the checkout, ``<repo>/.jax_cache``: the directory is
part of each entry's key, so a temp name, a pid or a timestamp would
never hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path.
    Call before the first compile."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
