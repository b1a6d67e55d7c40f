"""JAX's persistent compilation cache, placed by the program's entry points.

``enable_compile_cache`` is called from the ``main`` of each entry point
(``chip_smoke.py``, ``benchmarks/run.py``, the examples) and never at
library import: importing ``repro`` leaves JAX's cache configuration
alone, so code that compiles for a described chip can keep the cache off.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout holding this package (<checkout>/src/repro/compile_cache.py)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    path is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, so a later run in the same
    checkout finds what an earlier one compiled.

    The minimum compile time for an entry is lowered to zero: the
    cycle-accurate simulator builds one executable per shape signature,
    and on a TPU v5e nearly all of those builds finish under JAX's default
    1 s threshold, which would leave them out of the cache and rebuild them
    on every run.
    """
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
