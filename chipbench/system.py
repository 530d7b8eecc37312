"""The system under test, as a configuration asks for it.

The only module of the benchmark that imports the program: it builds
``DurableEngine(OnlineEngine(...))`` with the engine's default options
from a configuration's schema, turns host rows into the ``Table``
batches the engine ingests, and turns on the program's compilation
cache.
"""
from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

import jax

import repro.core  # noqa: F401  (imported before repro.data, which needs it)
from repro.core import CoarsenSpec, DurableEngine, OnlineEngine
from repro.data.columnar import Table
from repro.launch import compile_cache

__all__ = ["build", "batch", "engine_columns", "enable_compile_cache"]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``.jax_cache`` at the
    checkout's root, or ``$JAX_COMPILATION_CACHE_DIR``), keeping every
    program however fast it compiled, so that only a cell's first run
    compiles."""
    path = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def coarsen_specs(cfg: Mapping) -> Dict[str, CoarsenSpec]:
    out = {}
    for name, c in cfg["coarsening"].items():
        if "categorical" in c:
            out[name] = CoarsenSpec.categorical(int(c["categorical"]))
        else:
            lo, hi, k = c["equal_width"]
            out[name] = CoarsenSpec.equal_width(lo, hi, int(k))
    return out


def engine_columns(cfg: Mapping):
    """Columns of every ingested row: coarsened dims, treatments, outcome."""
    return (*cfg["coarsening"], *sorted(cfg["treatments"]), cfg["outcome"])


def build(cfg: Mapping, wal_dir: str) -> DurableEngine:
    """A durable engine of ``cfg``'s schema with default options: every
    batch journaled to the WAL in ``wal_dir`` and fsynced before its
    commit acknowledges it."""
    engine = OnlineEngine(coarsen_specs(cfg),
                          {t: tuple(c) for t, c in cfg["treatments"].items()},
                          cfg["outcome"], query_dims=tuple(cfg["query_dims"]))
    return DurableEngine(engine, wal_dir)


def batch(cols: Mapping[str, np.ndarray]) -> Table:
    """A host batch as the engine's ``Table`` (every row valid)."""
    n = len(next(iter(cols.values())))
    return Table.from_numpy(dict(cols), np.ones(n, bool))
