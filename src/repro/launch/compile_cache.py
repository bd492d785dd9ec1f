"""Persistent XLA compilation cache for the entry points.

Every entry point that compiles for a device (``launch/serve.py``, the
``benchmarks/`` scripts, ``chip_smoke.py``) calls ``enable_compile_cache``
once, before its first compile.  Library code and tests never do: a test
run leaves JAX's cache configuration as it found it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# one fixed path inside the checkout (listed in .gitignore): a cache that
# moves between runs is never found again, so no temp name, pid or time
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
    cache stays there: nothing is overridden.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
