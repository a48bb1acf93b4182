"""JAX's persistent compilation cache for the entry points.

``enable()`` is called by the program's entry points (``chip_smoke.py`` and
the ``train``/``serve`` mains) before their first compile -- never at import
and never in tests, so importing the library changes no JAX setting.

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
    in code.
  * Unset: the cache goes to ``<repo>/.jax_cache`` (git-ignored).  The path
    is fixed because it is part of the cache key: a cache directory that
    moves between runs never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
