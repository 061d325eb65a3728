"""Persistent XLA compilation cache for the entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and ``examples/serve_lm.py`` call
:func:`enable` once at start-up.  Nothing calls it on import or in tests.
"""
from __future__ import annotations

import os
from pathlib import Path

#: fixed, so a later run in this checkout finds what an earlier one compiled
#: (the cache directory is part of the cache's key)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at a directory; returns it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and it
    is left alone; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
