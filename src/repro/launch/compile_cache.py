"""Where JAX keeps its persistent compilation cache.

A cold start compiles every step of a 22-layer model; the persistent cache
lets later processes on the same machine skip that.  JAX keys cache entries
on the directory, so the directory must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
    overrides it.
  * otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Every entry point (``repro.launch.serve``, ``repro.launch.train``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable` before its
first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
