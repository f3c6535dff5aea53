"""One place that decides where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``
and the examples) call :func:`enable_compile_cache` once, before their first
compile. Library imports never do, so tests run with whatever cache the
environment gives them.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set in
  code.
* Unset: the cache goes to ``.jax_cache/`` at the root of this checkout (git
  ignores it). The path is fixed, never temporary or per-process, so a
  second run in the same checkout finds what the first one compiled.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
