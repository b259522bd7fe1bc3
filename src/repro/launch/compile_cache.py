"""JAX's persistent compilation cache, placed for entry points.

Each entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/server.py``, ``launch/train.py``, ``benchmarks/run.py``) calls
``enable()`` before its first compile; library code never does.  The cache
key includes the directory, so the directory must not move between runs.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: src/repro/launch/ -> three levels up is the root
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the cache on and return its directory.  Where ``ENV`` is set,
    JAX reads it itself and no directory is set in code; otherwise the
    fixed in-checkout ``DEFAULT_DIR`` is used."""
    import jax
    if os.environ.get(ENV):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
