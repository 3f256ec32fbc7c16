"""Where JAX keeps its persistent compilation cache.

The program entry points (``llmc``, ``benchmarks/run.py``, ``chip_smoke.py``)
call ``configure_compile_cache`` as they start. Importing this module sets
nothing, so library users and the tests keep JAX's defaults.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: A fixed directory inside the checkout. A cache found again is a cache
#: hit only if the directory does not move between runs, so the path comes
#: from nothing that changes per run: no temporary name, pid or time.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache is ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
