"""Where the persistent XLA/Mosaic compilation cache lives.

One rule for every entry point that drives the chip (``chip_smoke.py``,
``benchmarks/run.py``, ``python -m repro.launch.pc_run``):

  * ``JAX_COMPILATION_CACHE_DIR`` set → JAX already reads it; nothing else
    is configured here;
  * otherwise → ``<checkout>/.jax_cache`` (git-ignored). The path is fixed
    because it is part of the cache key: a directory built from a tmp dir,
    a pid or the time would never be hit twice.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fallback cache directory, inside the checkout
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
