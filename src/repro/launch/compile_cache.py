"""JAX's persistent compilation cache for the entry scripts.

Called by the scripts a user runs (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/run.py``) before their first compile — never at import and
never from tests, so library code and the test suite keep JAX's defaults.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the fixed in-checkout cache directory used when the environment names
#: none; listed in .gitignore.  A fixed path matters: the directory is part
#: of every cache key, so a cache that moves never hits.
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets no directory of its own; otherwise the cache lives at
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
