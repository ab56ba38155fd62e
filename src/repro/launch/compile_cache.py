"""Persistent compilation cache for the entry points.

``enable()`` is called by the programs users run (``chip_smoke.py``,
``launch/serve.py``, ``launch/train.py``), never on import of the
library.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it; otherwise the cache lives at the
fixed ``<checkout>/.jax_cache``.  The directory is part of each entry's
key, so it is never built from a temporary name, a process id or the
time: a second run of the same program finds the first run's programs.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
