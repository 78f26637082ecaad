"""Persistent compilation cache placement for the entry points.

Called at start-up by ``chip_smoke.py`` and the ``launch.train`` /
``launch.serve`` mains, never at import.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX has already read it and nothing here overrides it; otherwise the
cache goes to ``<checkout>/.jax_cache``.  That path is fixed on purpose:
it is part of each entry's key, so a directory that moved would never hit.
From here on, compiles and cache loads are recorded as spans
(``repro.core.spans.record_compiles``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.core.spans import record_compiles

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    record_compiles()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
