"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called first thing by ``launch.serve.main``,
``launch.train.main`` and ``chip_smoke.py``. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here. Otherwise, on a TPU, the
cache goes to ``.jax_cache/`` in the checkout: a fixed path, because the path
is part of what a later run must find again. CPU runs (tests, rehearsals)
compile in seconds and are left without a cache.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from repro import CHECKOUT_DIR

DEFAULT_DIR = os.path.join(CHECKOUT_DIR, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on; returns its directory, or None
    where this leaves it off."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
