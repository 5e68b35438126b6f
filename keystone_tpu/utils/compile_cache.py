"""Where the persistent XLA compilation cache lives.

One rule for every entry point that compiles for a device
(``python -m keystone_tpu <app>`` / ``serve``, ``chip_smoke.py``,
the probes and profilers under ``tools/``): when the environment names a
directory in ``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and
nothing is set in code; otherwise the cache is ``<checkout>/.xla_cache``.
The path is part of the cache's key, so a directory that moves never
hits: it carries no pid, timestamp or temporary name.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".xla_cache")


def enable_compile_cache() -> str:
    """Point JAX at the compile cache and return its directory. Call it
    from an entry point before the first compile; importing a module
    must never turn on disk-cache side effects."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
