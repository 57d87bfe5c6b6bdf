"""Where JAX keeps its persistent compile cache for this repository's programs.

``configure_compile_cache()`` is called once at start-up by the programs
that compile at deployment size (``chip_smoke.py``, ``benchmarks/run.py``).
Importing this module touches no JAX backend.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place JAX's persistent compile cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it
    itself and nothing else is set here.  Otherwise the cache goes to the
    fixed ``<repo>/.jax_cache`` — never a temporary, pid- or time-derived
    path, so a later run of the same checkout finds its entries again.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
