"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/common.run_main``, the
``repro.launch`` CLIs) call :func:`use_compile_cache` once, before their
first compile; library code never does.  A directory that moves between
runs never hits, so the fallback is one fixed path in the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (git-ignored); this file is
# <checkout>/src/repro/launch/compile_cache.py.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other directory is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
