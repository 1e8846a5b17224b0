"""JAX's persistent compilation cache, kept at one fixed place.

``enable_compile_cache()`` is called by every entry point that compiles
the engine (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve_graph``):

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it, and no
  other directory is set here;
* otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path,
  because the path is part of what a later run must find again (never a
  temp, pid or time-based directory).  ``.gitignore`` lists it.

Every compile is cached, not only those past JAX's default one-second
threshold: a run re-compiles dozens of small programs (kernels, sweep
chunks, history drains).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
