"""Where JAX's persistent compilation cache lives.

The entry points that compile the main path at real sizes
(``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile, so a
second run of the same programs loads them instead of compiling again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  else is set here — the cache is placed from outside.
* Not set: the cache goes to ``.jax_cache/`` at the root of the
  checkout (listed in ``.gitignore``).  The path is fixed — never a
  temporary directory, a process id or a time — so every run of the
  same checkout finds the same cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "compile_cache_dir",
           "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir(environ=None) -> str:
    """The cache directory for ``environ`` (default ``os.environ``)."""
    environ = os.environ if environ is None else environ
    return environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on (see module docstring); returns its
    directory."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
