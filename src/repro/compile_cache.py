"""JAX's persistent compilation cache for the entry points.

A cold process compiles every program again; the persistent cache lets
later processes on the same machine load them instead.  The entry points
(``chip_smoke.py``, ``repro.launch.*``, ``examples/*``) call
``enable_compile_cache`` once before they build anything.  Tests keep the
cache off (``tests/conftest.py``).
"""

from __future__ import annotations

import os
import pathlib

import jax

# fixed, never derived from a temp name, pid or time: the directory is part
# of what a later process must find again
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs in ``<repo root>/.jax_cache``, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX then uses that directory and
    this sets nothing."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
