"""``repro.launch.trace``: the per-label dispatch counter behind every
1-dispatch assertion in the suite — labels, nesting, snapshots, batch
amortization accounting, and the jit-attribute preservation the engines
rely on — and the recorder of spans, counters, compile seconds and
program stages: off (nothing kept) without a profiler session, the span
tree of one durable ingest under one, and the stages of the fused ingest
program found in its compiled instructions.
"""
import glob
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (CoarsenSpec, DurableEngine, OnlineEngine,
                        PartitionedOnlineEngine)
from repro.core import wal as wal_mod
from repro.data.columnar import Table
from repro.launch import trace
from repro.launch.trace import (batched_served, count_dispatches,
                                counted_jit, dispatch_count,
                                dispatch_counts, hot_path, record_batch,
                                record_dispatch)


def test_counted_jit_counts_each_call():
    f = counted_jit(lambda x: x + 1)
    with count_dispatches() as n:
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))
    assert n() == 2


def test_counted_jit_label_attribution():
    f = counted_jit(lambda x: x * 2, label="alpha")
    g = counted_jit(lambda x: x * 3, label="beta")
    h = counted_jit(lambda x: x * 5)          # unlabeled
    x = jnp.ones((3,))
    before = dispatch_counts()
    with count_dispatches() as total, \
            count_dispatches(label="alpha") as na, \
            count_dispatches(label="beta") as nb:
        f(x)
        f(x)
        g(x)
        h(x)
    assert total() == 4
    assert na() == 2 and nb() == 1
    after = dispatch_counts()
    assert after.get("alpha", 0) - before.get("alpha", 0) == 2
    assert after.get("beta", 0) - before.get("beta", 0) == 1


def test_nested_and_overlapping_label_windows():
    f = counted_jit(lambda x: x + 1, label="outer")
    g = counted_jit(lambda x: x + 2, label="inner")
    x = jnp.zeros((2,))
    with count_dispatches() as total:
        f(x)
        with count_dispatches(label="inner") as ni:
            g(x)
            with count_dispatches(label="outer") as no:
                f(x)
            assert no() == 1          # only the f() inside its window
            g(x)
        assert ni() == 2              # both g() calls, not the f()s
    assert total() == 4


def test_record_dispatch_manual_accounting():
    start = dispatch_count()
    start_l = dispatch_count("manual")
    record_dispatch(3, label="manual")
    assert dispatch_count() - start == 3
    assert dispatch_count("manual") - start_l == 3


def test_record_batch_amortization_ratio():
    served = batched_served("bq")
    with count_dispatches(label="bq") as n:
        prog = counted_jit(lambda x: x.sum(axis=0), label="bq")
        prog(jnp.ones((8, 3)))
        record_batch(8, label="bq")
    assert n() == 1
    assert batched_served("bq") - served == 8


def test_unknown_label_counts_zero():
    assert dispatch_count("no-such-label") == 0
    assert batched_served("no-such-label") == 0


def test_counted_jit_preserves_jit_attributes():
    @counted_jit
    def f(x):
        return x * x

    assert f._cache_size() == 0
    f(jnp.arange(4.0))
    assert f._cache_size() == 1
    f(jnp.arange(4.0))
    assert f._cache_size() == 1       # no retrace on the same shape
    lowered = f.lower(jnp.arange(4.0))
    assert "jit" in lowered.as_text().lower() or lowered is not None


def test_counted_jit_forwards_jit_kwargs():
    @counted_jit
    def plain(x):
        return x

    f = counted_jit(lambda x, k: x * k, static_argnames=("k",))
    assert float(f(jnp.float32(2.0), k=3)) == 6.0
    g = counted_jit(lambda s: {k: v + 1 for k, v in s.items()},
                    donate_argnums=(0,))
    state = {"a": jnp.arange(3.0)}
    out = g(state)
    assert np.allclose(np.asarray(out["a"]), [1.0, 2.0, 3.0])
    with pytest.raises(RuntimeError):
        np.asarray(state["a"])        # donated: buffer deleted
    assert float(plain(jnp.float32(1.0))) == 1.0


def test_hot_path_marker_is_noop_at_runtime():
    @hot_path
    def body(x):
        return x + 1

    assert body.__hot_path__ is True
    assert body(41) == 42


# ------------------------------------------------------ spans and stages
SPECS = {"x0": CoarsenSpec.categorical(5), "x1": CoarsenSpec.categorical(4)}


def _batch(n, seed, x0_hi=5):
    rng = np.random.default_rng(seed)
    cols = {"x0": rng.integers(0, x0_hi, n).astype(np.int32),
            "x1": rng.integers(0, 4, n).astype(np.int32)}
    cols["ta"] = (rng.random(n) < 0.4).astype(np.int32)
    cols["y"] = np.round(rng.normal(0, 2, n)).astype(np.float32)
    return Table.from_numpy(cols, np.ones(n, bool))


def _durable(tmp_path, name="d", granule=64, n_parts=0):
    kw = dict(granule=granule, delta_granule=granule)
    eng = (PartitionedOnlineEngine(SPECS, {"ta": ["x0", "x1"]}, "y",
                                   n_parts=n_parts, **kw) if n_parts
           else OnlineEngine(SPECS, {"ta": ["x0", "x1"]}, "y", **kw))
    return DurableEngine(eng, str(tmp_path / name))


@pytest.fixture
def profiled(tmp_path):
    """A profiler session around the test body; yields its trace dir."""
    trace.clear_spans()
    log_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def test_no_profiler_session_keeps_nothing(tmp_path, monkeypatch):
    def no_annotation(*a, **kw):
        raise AssertionError("annotation opened with no profiler session")
    monkeypatch.setattr(trace, "TraceAnnotation", no_annotation)
    trace.clear_spans()
    dur = _durable(tmp_path)
    for seed in range(3):
        dur.ingest(_batch(64, seed))
        dur.commit()
    assert trace.span("x") is trace.span("y", seq=1)   # one shared no-op
    assert trace.spans() == []
    assert trace.counters() == {}
    assert trace.compile_seconds_before_recording() is None
    assert trace.op_stages("ingest") == {}
    dur.close()


def test_durable_ingest_span_tree_and_host_plane(tmp_path):
    dur = _durable(tmp_path)
    dur.ingest(_batch(64, 0))            # compiles outside the session
    dur.commit()
    trace.clear_spans()
    log_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(log_dir)
    try:
        dur.ingest(_batch(64, 1, x0_hi=2))   # known keys: no growth
        dur.commit()
    finally:
        jax.profiler.stop_trace()
    dur.close()
    recs = trace.spans()
    by_id = {sp.id: sp for sp in recs}
    tree = sorted((sp.name, by_id[sp.parent].name if sp.parent else None)
                  for sp in recs)
    assert tree == sorted([
        ("durable.ingest", None),
        ("engine.validate", "durable.ingest"),
        ("wal.append", "durable.ingest"),
        ("wal.fsync", "wal.append"),
        ("engine.ingest", "durable.ingest"),
        ("engine.validate", "engine.ingest"),
        ("engine.pad", "engine.ingest"),
        ("engine.dispatch", "engine.ingest"),
        ("engine.verdict_wait", "engine.ingest"),
        ("engine.bookkeep", "engine.ingest"),
        ("durable.commit", None),
        ("engine.commit", "durable.commit")])
    assert {sp.seq for sp in recs} == {2}       # the batch's WAL seq
    root = next(sp for sp in recs if sp.name == "durable.ingest")
    assert root.attrs == {"rows": 64, "retract": False, "seq": 2}
    own = trace.self_times(recs)
    assert all(v >= 0 for v in own.values())
    for top in (sp for sp in recs if sp.parent is None):
        inside = [sp for sp in recs if sp.start_ns >= top.start_ns
                  and sp.end_ns <= top.end_ns]
        assert sum(own[sp.id] for sp in inside) == top.end_ns - top.start_ns
    data = jax.profiler.ProfileData.from_file(
        glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                  recursive=True)[0])
    host = {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert {sp.name for sp in recs} <= host


def test_wal_and_redispatch_counters(tmp_path, profiled):
    """The recorder's counters, kept while a session records: a log's own
    appends count as records and bytes, a follower's journaled copy of a
    shipped record does not; every fsync counts."""
    dur = _durable(tmp_path, granule=16)
    dur.ingest(_batch(64, 0, x0_hi=2))
    dur.commit()
    dur.ingest(_batch(64, 1))        # more groups than delta and views hold
    dur.commit()
    got = trace.counters()
    assert got["wal.records"] == 2 and got["wal.fsyncs"] == 2
    assert got["wal.bytes"] > 2 * 64 * 16
    assert got["ingest.redispatches"] >= 1
    assert got["ingest.redispatches"] == sum(
        sp.name == "engine.grow" for sp in trace.spans())
    follower = wal_mod.BatchLog(str(tmp_path / "follower"))
    for rec in dur.wal.read():
        follower.append_record(rec)
    follower.close()
    dur.close()
    after = trace.counters()
    assert after["wal.records"] == 2 and after["wal.bytes"] == got["wal.bytes"]
    assert after["wal.fsyncs"] == 4


def test_compile_listener_counts_fresh_jit(profiled):
    before = trace.compile_seconds()
    x = jnp.arange(5.0)
    with trace.span("outer") as sp:
        jax.jit(lambda v: jnp.sort(v) * 7.25 + 0.5)(x).block_until_ready()
    assert trace.compile_seconds() > before
    # what was compiled before the first recorded span, not after
    assert trace.compile_seconds_before_recording() == before
    outer = next(r for r in trace.spans() if r.name == "outer")
    assert sp.id == outer.id
    compiles = [r for r in trace.spans() if r.name == "compile"
                and r.start_ns >= outer.start_ns]
    assert compiles and all(r.parent == outer.id for r in compiles)


@pytest.mark.parametrize("n_parts", [0, 2])
def test_op_stages_maps_fused_ingest_program(tmp_path, profiled, n_parts):
    """Every op of a tiny fused ingest program (replicated, or partitioned
    with its merges under ``vmap``) compiled on the CPU that the program's
    own code produced (it carries ``op_name`` metadata of the program)
    maps to a known stage; control flow is not a leaf."""
    dur = _durable(tmp_path, n_parts=n_parts)
    dur.ingest(_batch(64, 0))            # new keys: the re-sort branch
    dur.commit()
    dur.close()
    ops = trace.op_stages("ingest")
    assert ops and trace.op_stages("ingest") is ops       # computed once
    stages = {op.stage for op in ops.values() if op.leaf}
    assert {"build", "probe", "resort", "relocate", "touch_remap",
            "overlap", "gate", "stream"} <= stages
    assert stages - {None} <= set(trace.INGEST_STAGES)
    (jitted, tree, sig), = trace._calls["ingest"].values()
    args, kwargs = jax.tree.unflatten(tree, sig)
    text = jitted.lower(*args, **kwargs).compile().as_text()
    skip = {"parameter", "constant", "tuple", "get-tuple-element",
            "bitcast"}
    n_program_ops = 0
    for line in text.splitlines():
        m = trace._HLO_OP.match(line)
        meta = trace._OP_NAME.search(line)
        if m is None or meta is None or m.group(2) in skip:
            continue
        if not meta.group(1).startswith("jit(ingest_program)/"):
            continue
        n_program_ops += 1
        op = ops[m.group(1)]
        assert op.leaf == (m.group(2) not in trace.CONTROL_OPCODES)
        if op.leaf:
            assert op.stage in trace.INGEST_STAGES, line
    assert n_program_ops > 100
    secs = trace.stage_seconds([(n, 1.0) for n, op in ops.items()
                                if op.stage == "probe"], "ingest")
    assert secs == {"probe": float(sum(
        op.stage == "probe" and op.leaf for op in ops.values()))}


def _program_ops(jitted, tree, sig):
    """Instruction name -> OpStage of one remembered program."""
    args, kwargs = jax.tree.unflatten(tree, sig)
    return trace.hlo_op_stages(jitted.lower(*args, **kwargs).compile()
                               .as_text())


def test_op_stages_unmaps_names_two_programs_share(tmp_path, profiled):
    """An ingest and a retraction in one recorded window run two ingest
    programs whose instruction names repeat; a name the two place in
    different stages maps to no stage, the others keep theirs."""
    dur = _durable(tmp_path)
    b = _batch(64, 0)
    dur.ingest(b)
    dur.commit()
    dur.ingest(b, retract=True)
    dur.commit()
    dur.close()
    calls = list(trace._calls["ingest"].values())
    assert len(calls) == 2
    one, two = (_program_ops(*c) for c in calls)
    ops = trace.op_stages("ingest")
    assert set(ops) == set(one) | set(two)
    clash = {n for n in set(one) & set(two) if one[n] != two[n]}
    assert any(one[n].stage != two[n].stage for n in clash)
    for name, op in ops.items():
        if name in clash:
            assert op.stage is None
        else:
            assert op == one.get(name, two.get(name))
    staged = [(n, 1.0) for n in clash if one[n].leaf or two[n].leaf]
    assert trace.stage_seconds(staged) == {None: float(len(staged))}


def test_stage_ms_per_batch_reads_nothing_past_the_unmapped_share(
        tmp_path, profiled):
    dur = _durable(tmp_path)
    dur.ingest(_batch(64, 0))
    dur.commit()
    dur.close()
    ops = trace.op_stages("ingest")
    probe = next(n for n, op in ops.items() if op.stage == "probe"
                 and op.leaf)
    none = next(n for n, op in ops.items() if op.stage is None and op.leaf)
    nested = next(n for n, op in ops.items() if not op.leaf)
    share = trace.UNMAPPED_SHARE
    pairs = [(probe, 1.0 - share), (none, share), (nested, 9.0),
             ("not_an_op", 9.0)]
    assert trace.stage_ms_per_batch(pairs, 2, ("probe",)) == pytest.approx(
        1e3 * (1.0 - share) / 2)
    assert trace.stage_ms_per_batch(pairs, 2, ("resort",)) == 0.0
    pairs[1] = (none, 1.01 * share)
    assert trace.stage_ms_per_batch(pairs, 2, ("probe",)) is None
    assert trace.stage_ms_per_batch([("not_an_op", 1.0)], 2,
                                    ("probe",)) is None
    assert trace.stage_ms_per_batch(pairs[:1], 0, ("probe",)) is None


def test_hlo_op_stages_parses_instruction_lines():
    text = "\n".join([
        '  %fusion.3 = (f32[8]{0:T(256)}, s32[]) fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(ingest_program)/view_a/probe/cond/'
        'branch_0_fun/resort/sort"}',
        '  ROOT %while.2 = s32[] while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(ingest_program)/view_a/probe/while"}',
        '  %copy.1 = f32[8]{0} copy(%x)',
        '  %sort.4 = s32[2,8]{1,0} sort(%y), metadata={op_name="jit('
        'ingest_program)/shard_map/view_a/probe/cond/branch_0_fun/'
        'vmap(resort)/sort"}',
        'ENTRY %main.5 (p: f32[8]) -> f32[8] {'])
    assert trace.hlo_op_stages(text) == {
        "fusion.3": trace.OpStage("resort", True),
        "while.2": trace.OpStage("probe", False),
        "copy.1": trace.OpStage(None, True),
        "sort.4": trace.OpStage("resort", True)}


def test_recorder_leaves_ingest_state_bit_identical(tmp_path):
    batches = [_batch(64, s) for s in range(4)]
    states = []
    for traced in (False, True):
        dur = _durable(tmp_path, f"e{int(traced)}")
        if traced:
            trace.clear_spans()
            jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            for b in batches:
                dur.ingest(b)
                dur.commit()
        finally:
            if traced:
                jax.profiler.stop_trace()
        states.append(dur.export_canonical())
        dur.close()
    assert trace.spans()
    off, on = (jax.tree_util.tree_leaves_with_path(s["views"])
               for s in states)
    assert [p for p, _ in off] == [p for p, _ in on]
    for (path, a), (_, b) in zip(off, on):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), path
