"""JAX's persistent compilation cache for the repository's entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` before their first compile, so a second
run in the same place reuses the compiled kernels and programs.
"""
from __future__ import annotations

import os

import jax

#: cache directory under the checkout when the environment names none
CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
    itself and nothing else is set here. Otherwise the cache is
    ``<root>/.jax_cache``: ``root`` is the checkout, and the path is
    fixed (never a temporary name, a pid or the time) so that later runs
    find what earlier ones wrote.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
