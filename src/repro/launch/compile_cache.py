"""JAX's persistent compilation cache for the command-line entry points."""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed path at the checkout root: runs from one checkout find each
# other's compiled programs
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across processes.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and
    nothing is set here. Otherwise the cache goes to ``<checkout>/.jax_cache``.
    The test suite turns the cache off (``JAX_ENABLE_COMPILATION_CACHE`` in
    ``tests/conftest.py``), so this changes nothing under the tests.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
