"""JAX's persistent compilation cache, placed from outside the program.

A cache entry is found again only from the same directory, so the
directory never depends on a temp name, a pid or the time:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; the cache
  lives there and no other directory is set in code;
* unset — the one fixed path ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``), so a second run from the same checkout hits what the
  first one compiled.

Entry points that run on the chip (``chip_smoke.py``, benchmarks) call
:func:`enable_compile_cache` once, before their first compile.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; -> its directory."""
    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    return cache_dir
