"""Where JAX keeps its persistent compilation cache.

The cache path is part of the cache's key, so it must not move between
runs: a directory built from a temporary name, a pid or the time never
hits. Entry points call :func:`configure` at the start of ``main()``
(never at import): ``JAX_COMPILATION_CACHE_DIR`` wins when it is set, and
otherwise the cache lives at ``<checkout>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
