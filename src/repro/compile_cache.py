"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``benchmarks.run`` and the benchmark
``main()``s) call :func:`use_compile_cache` once, before they compile
anything.  Importing the library never does, so tests run without a cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set.
* Unset: the cache sits at ``<checkout>/.jax_cache`` (gitignored).  The
  directory is part of what a later process must name to find an entry, so
  it is one fixed path, never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout's cache directory (``src/repro/`` -> checkout root)
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
