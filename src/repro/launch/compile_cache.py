"""Persistent compilation cache for the entry points.

Only entry points call ``use_compile_cache`` (the ``main()`` of
``launch/train.py`` and ``launch/serve.py``, and ``chip_smoke.py``), before
their first compile; importing a module never turns the cache on, and the
tests leave it off.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, since the path is part of what a later run looks up."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
