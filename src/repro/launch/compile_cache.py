"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the ``examples/``
mains) call :func:`enable_compile_cache` once before they compile
anything. Importing ``repro`` never does: a library must not choose where
its caller's process writes.

The rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this module sets no other directory. Otherwise the cache lives in
``.jax_cache/`` at the checkout root — a fixed path, because the path is
part of what a later run must find again (a temporary, per-pid or
time-stamped directory would never hit).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory (see the module docstring for the rule)."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
