"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks.run``, the examples) call
``configure_compile_cache()`` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here.  Otherwise the cache lives at ``<checkout>/.jax_cache`` (listed in
``.gitignore``): a fixed path, because the directory is part of what makes a
later process on the same checkout find its entries.
"""

from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory: <repo>/.jax_cache
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
