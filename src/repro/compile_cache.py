"""JAX's persistent compilation cache, as the entry points turn it on.

``python -m repro.cli``, ``chip_smoke.py`` and the benchmark scripts call
:func:`enable_compile_cache` under their ``__main__`` guard, before anything
compiles; library code and the tests never do.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and the cache
  lives there; no other path is set here.
* otherwise: the fixed ``<checkout>/.jax_cache`` (git-ignored).  The path
  never depends on the process, the time or a temporary directory, so a
  later run in the same checkout finds what an earlier one compiled.

JAX's own ``JAX_ENABLE_COMPILATION_CACHE=false`` still turns the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
