"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``), applied by calling :func:`enable_compile_cache`
when the program starts, never at import:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself and
  nothing is set in code;
* otherwise the cache goes to ``.jax_cache`` at the root of the checkout,
  which ``.gitignore`` lists.  The path is fixed — never built from a
  temporary name, a pid or the time — because a cache whose directory
  moves between runs never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory the cache uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
