"""Compile the online engine's device programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, installed alongside jax,
compiles for a v5e that is described (``v5e:2x2``) and not attached, and
raises what the chip's compiler would raise — misaligned Pallas blocks,
VMEM overruns, programs that do not fit HBM. Arguments are
``ShapeDtypeStruct``s built from a small CPU engine's state tree of the
smoke's FLIGHTDELAY schema (``repro.launch.smoke``), resized to
:data:`CAPACITY` view slots.

The topology is described inside a module-scoped fixture, never at
import, so every xdist worker collects the same tests and only the worker
that runs this file loads the TPU library. The persistent compile cache is
off around the compiles (an entry written here could not be read back
without a chip).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import OnlineEngine, PartitionedOnlineEngine, fused
from repro.kernels.segment_stats import (scatter_merge_pallas,
                                         scatter_merge_parts_pallas)
from repro.launch import smoke

#: view slots every program is compiled at. The v5e compiler builds a
#: sort of 2^13 slots in ~1 s and one of 2^16 in ~30 s, and every program
#: here sorts view + delta slots, so 2^12 keeps the whole file well under
#: a minute (the chip smoke's views hold 2^18-2^19; chip_smoke.py is the
#: full-size check)
CAPACITY = 1 << 12
BATCH_ROWS = 1 << 11          # streamed batch rows
WAVE = 256                    # specs in one batched query
HBM_BYTES = 16 * 10 ** 9      # one v5e chip
INT_COLS = {"airport", "carrier", *smoke.COVARIATES}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("data",))


def _abstract(tree, sharding, slots=None):
    """ShapeDtypeStructs of ``tree``; the last axis resized to ``slots``."""
    def one(x):
        shape = tuple(x.shape)
        if slots is not None:
            shape = shape[:-1] + (slots,)
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)
    return jax.tree.map(one, tree)


def _batch(engine, sharding, rows=BATCH_ROWS):
    cols = {c: jax.ShapeDtypeStruct(
        (rows,), jnp.int32 if c in INT_COLS else jnp.float32,
        sharding=sharding) for c in engine._row_cols}
    return cols, jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=sharding)


def _scalar(sharding):
    return jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)


def _fits(compiled) -> None:
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BYTES, ma


def test_fused_ingest_compiles_for_one_chip(one_chip):
    eng = smoke.make_engine(OnlineEngine, smoke.FULL)
    eng._delta_cap = BATCH_ROWS
    state = eng._pack_view_state()
    args = (*_batch(eng, one_chip),
            dict(views=_abstract(state["views"], one_chip, CAPACITY),
                 stream=_abstract(state["stream"], one_chip)),
            _scalar(one_chip), _scalar(one_chip))
    compiled = eng._fused_program(False).lower(*args).compile()
    _fits(compiled)


def test_single_query_compiles_for_one_chip(one_chip):
    # the program with the subpopulation in its trace (the assemble path)
    eng = smoke.make_engine(OnlineEngine, smoke.FULL)
    t = sorted(eng.treatments)[0]
    view = eng.views[t]
    tab = view.table
    prog = fused.get_fused_query(tab.codec, t, (("airport", (3, 17)),))
    stats = {k: tab.stats[k] for k in fused.query_stat_names(t)}
    args = _abstract((tab.key_hi, tab.key_lo, stats, tab.group_valid,
                      view.keep), one_chip, CAPACITY)
    _fits(prog.lower(*args).compile())


@pytest.mark.parametrize("n_specs", [1, WAVE])
def test_fused_query_compiles_for_one_chip(one_chip, n_specs):
    # n_specs=1 is an uncached ate(), WAVE a batched ate_batch wave
    eng = smoke.make_engine(OnlineEngine, smoke.FULL)
    prog = fused.get_fused_query_batch(eng._batch_view_schema(),
                                       eng._spec_cards(), n_specs, None,
                                       "data", False)
    states = _abstract(tuple(eng._view_query_args(t)
                             for t in sorted(eng.treatments)),
                       one_chip, CAPACITY)
    width = fused.SPEC_META_WORDS + fused.spec_word_layout(
        eng._spec_cards())[1]
    rows = jax.ShapeDtypeStruct((n_specs, width), jnp.uint32,
                                sharding=one_chip)
    _fits(prog.lower(states, rows).compile())


def test_scatter_merge_pallas_compiles_at_4096_slots(one_chip):
    c, s, b = 4096, 128, 256
    fn = jax.jit(functools.partial(scatter_merge_pallas, block=b,
                                   interpret=False))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((c, s), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((4 * b,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((4 * b, s), jnp.float32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scatter_merge_parts_pallas_compiles(one_chip):
    n_parts, c, s, b = 2, 2048, 128, 256
    fn = jax.jit(functools.partial(scatter_merge_parts_pallas, block=b,
                                   interpret=False))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((n_parts, c, s), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n_parts, 4 * b), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((n_parts, 4 * b, s), jnp.float32,
                             sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_ingest_parts_compiles_on_2x2_mesh(mesh4):
    n_parts = 8
    eng = smoke.make_engine(PartitionedOnlineEngine, smoke.FULL,
                            n_parts=n_parts)
    state = eng._pack_view_state()
    part = NamedSharding(mesh4, P("data", None))
    rep = NamedSharding(mesh4, P())
    prog = fused.get_fused_ingest_parts(
        eng.codec, tuple(sorted(eng.specs.items())),
        tuple(sorted(eng.treatments)), eng._fused_view_dims(), eng.outcome,
        eng._fused_caps(), BATCH_ROWS, n_parts, mesh4, "data", False,
        False, eng._stream_names(), eng.seed)
    args = (*_batch(eng, NamedSharding(mesh4, P("data"))),
            dict(views=_abstract(state["views"], part,
                                 CAPACITY // n_parts),
                 stream=_abstract(state["stream"], rep)),
            _scalar(rep), _scalar(rep))
    compiled = prog.lower(*args).compile()
    _fits(compiled)
    assert "all-to-all" in compiled.as_text()
