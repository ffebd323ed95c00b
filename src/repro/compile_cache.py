"""Where JAX keeps its persistent compilation cache.

A process that compiles a program JAX has compiled before — same program,
same backend, same JAX — loads the executable from this cache instead of
compiling again. The cache's location is part of how it is found, so it must
not move between runs: when ``JAX_COMPILATION_CACHE_DIR`` is set JAX uses that
directory and nothing here overrides it; otherwise the cache lives in one
fixed directory inside the checkout, ``<checkout>/.jax_cache`` (listed in
``.gitignore``).

:func:`configure_compile_cache` runs at the entry points — ``repro.connect``
and ``chip_smoke.py``. JAX fixes the cache's location at the first compile of
the process, so a call after that changes nothing.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/compile_cache.py -> the checkout root
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory: ``$JAX_COMPILATION_CACHE_DIR`` (or a directory already
    configured in code) as it is, else :data:`DEFAULT_DIR`."""
    configured = os.environ.get(ENV_VAR) or jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
