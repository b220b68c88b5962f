"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``tests/goldens/regen.py``) call :func:`use_checkout_cache` before their
first compile; library modules never place the cache.
"""

from __future__ import annotations

import os

import jax


def use_checkout_cache(root: str) -> str:
    """Place the compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and nothing is changed.  Otherwise the cache goes to
    ``<root>/.jax_cache``: a fixed path inside the checkout, because the
    path is part of what a cache entry is found by.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
