"""JAX's persistent compilation cache, placed for the entry points.

``launch.serve``, ``launch.train`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before they compile anything, so a second
run on the same machine reads its executables back instead of compiling
them again.  Importing ``repro`` does not do this.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
  other directory is set here;
* otherwise ``.jax_cache/`` at the root of this checkout (git ignores
  it).  The path is fixed, never derived from a temp name, a process id
  or the time: it is part of the cache key, so a directory that moves
  never hits.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
