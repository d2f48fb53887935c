"""Where JAX's persistent compilation cache lives.

The cache directory is part of the cache key, so it must be the same
path on every run.  ``JAX_COMPILATION_CACHE_DIR`` places it from outside
(JAX reads the variable itself — nothing is set in code then); otherwise
it is ``<checkout>/.jax_cache`` (git-ignored).  Called by the entry
points that compile for the device (``benchmark/run.py``,
``chip_smoke.py``, the ``scripts/`` tools, ``ps.worker_main``), never at
package import.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
