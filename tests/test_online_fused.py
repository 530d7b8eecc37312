"""Single-dispatch fused ingest: the whole delta pipeline as ONE program.

Contracts under test:

  * STEADY STATE IS ONE DISPATCH — once shapes stabilize, every ingest of
    both engines issues exactly one compiled-program launch (the
    ``repro.launch.trace`` counter) and never retraces (the program's jit
    cache size stays constant).
  * DONATION IS REAL — the fused program donates the state buffers: the
    pre-ingest arrays are dead after the call (in-place update, not
    copy-merge-copy), yet a failed retraction still leaves the LOGICAL
    state untouched (pass-through outputs).
  * GROWTH STAYS ON DEVICE — novel keys that fit the current capacity take
    the in-program re-sort branch (no recompile); keys beyond capacity
    trigger the capacity-doubling recompile and a second dispatch, after
    which the steady state is one dispatch again.
  * TOUCH-STAMP RENORMALIZATION — the int32 ingest counter renormalizes
    (subtract min live stamp) before it can wrap, preserving TTL eviction
    semantics.
  * K-PARTITIONS-PER-DEVICE — ``n_parts`` may exceed the device count;
    hash-skewed streams keep every partition's occupancy under capacity.
  * the fused Pallas scatter-merge-parts kernel matches the vmapped oracle.
  * THE RE-SORT MERGE'S SLOT MAP IS EXACT — touch stamps carried by the
    grouping's own permutation equal, bit for bit, those found by binary
    search of every old and delta key, and batches that do not commit
    pass the state through unchanged.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import CoarsenSpec, OnlineEngine, PartitionedOnlineEngine
from repro.core import cube, fused, groupby
from repro.core.online import BASE_VIEW
from repro.data.columnar import Table
from repro.launch.trace import count_dispatches

SPECS = {"x0": CoarsenSpec.categorical(5), "x1": CoarsenSpec.categorical(4),
         "x2": CoarsenSpec.categorical(3)}
TREATMENTS = {"ta": ["x0", "x1"], "tb": ["x0", "x2"]}


def _frame(n, seed=0, x0_hi=5):
    rng = np.random.default_rng(seed)
    cols = {
        "x0": rng.integers(0, x0_hi, n).astype(np.int32),
        "x1": rng.integers(0, 4, n).astype(np.int32),
        "x2": rng.integers(0, 3, n).astype(np.int32),
    }
    cols["ta"] = (rng.random(n) < 0.2 + 0.5 * cols["x0"] / 4).astype(
        np.int32)
    cols["tb"] = (rng.random(n) < 0.4).astype(np.int32)
    y = 2.0 * cols["ta"] + 1.5 * cols["x0"] + rng.normal(0, 0.5, n)
    cols["y"] = np.round(y).astype(np.float32)
    return cols, rng.random(n) > 0.08


def _stat_map(cub):
    gv = (np.asarray(cub.group_valid)
          & (np.asarray(cub.stats["one"]) != 0)).reshape(-1)
    hi = np.asarray(cub.key_hi).reshape(-1)[gv]
    lo = np.asarray(cub.key_lo).reshape(-1)[gv]
    c = {k: np.asarray(v).reshape(-1)[gv]
         for k, v in sorted(cub.stats.items())}
    return {(int(h), int(l)): tuple(float(c[k][i]) for k in c)
            for i, (h, l) in enumerate(zip(hi, lo))}


def _batches(n_batches, size, seed0=100, x0_hi=5):
    out = []
    for i in range(n_batches):
        cols, valid = _frame(size, seed=seed0 + i, x0_hi=x0_hi)
        out.append(Table.from_numpy(cols, valid))
    return out


@pytest.mark.parametrize("make", [
    lambda: OnlineEngine(SPECS, TREATMENTS, "y", granule=256),
    lambda: PartitionedOnlineEngine(SPECS, TREATMENTS, "y", granule=256,
                                    n_parts=3),
])
def test_steady_state_is_one_dispatch_and_no_retrace(make):
    eng = make()
    feed = _batches(6, 500)
    for b in feed[:3]:
        eng.ingest(b)            # warm: traces + capacity settle
    prog = eng._fused_program(False)
    cache_before = prog._cache_size()
    for b in feed[3:]:
        # bucket-pad OUTSIDE the guard: the transfer-clean contract covers
        # bucket-sized steady-state batches (non-bucket sizes pay the
        # documented eager jnp.pad pre-step, which materializes constants)
        b = eng._bucket_pad(b)
        with count_dispatches() as n, jax.transfer_guard("disallow"):
            eng.ingest(b)
        assert n() == 1, f"steady-state ingest issued {n()} dispatches"
    assert prog._cache_size() == cache_before, "steady-state ingest retraced"


def test_fused_state_buffers_are_donated_in_place():
    eng = OnlineEngine(SPECS, TREATMENTS, "y", granule=256)
    feed = _batches(3, 400)
    eng.ingest(feed[0])
    old_stats = eng.base.stats["one"]   # keep a reference, then ingest
    eng.ingest(feed[1])
    with pytest.raises(RuntimeError):
        _ = np.asarray(old_stats)       # donated: buffer is dead
    # and the new state is alive and correct
    assert int(eng.base.n_groups()) > 0


def test_failed_retraction_passes_state_through_unchanged():
    eng = OnlineEngine(SPECS, TREATMENTS, "y", granule=256)
    cols, valid = _frame(800, seed=7)
    eng.ingest(Table.from_numpy(cols, valid))
    before = _stat_map(eng.base)
    bogus = Table.from_numpy({k: np.repeat(v[:1], 300) for k, v in
                              cols.items()}, np.ones(300, bool))
    with pytest.raises(ValueError, match="never ingested"):
        eng.ingest(bogus, retract=True)
    # donated buffers were swapped for pass-through outputs: values equal
    assert _stat_map(eng.base) == before
    # and the engine still ingests normally afterwards
    eng.ingest(Table.from_numpy(cols, valid))


def test_in_program_growth_and_capacity_doubling_recompile():
    # granule=64 but the key space holds 240 combos: the stream must grow
    # capacity mid-stream (recompile) and keep the state exact vs offline
    specs = {"x0": CoarsenSpec.categorical(8),
             "x1": CoarsenSpec.categorical(6),
             "x2": CoarsenSpec.categorical(5)}
    treatments = {"t": ["x0", "x1", "x2"]}
    rng = np.random.default_rng(0)

    def frame(n, seed):
        r = np.random.default_rng(seed)
        c = {"x0": r.integers(0, 8, n).astype(np.int32),
             "x1": r.integers(0, 6, n).astype(np.int32),
             "x2": r.integers(0, 5, n).astype(np.int32)}
        c["t"] = (r.random(n) < 0.5).astype(np.int32)
        c["y"] = np.round(r.normal(0, 1, n)).astype(np.float32)
        return c

    del rng
    eng = OnlineEngine(specs, treatments, "y", granule=64,
                       delta_granule=1024)
    frames = [frame(600, seed=i) for i in range(4)]
    for c in frames:
        eng.ingest(Table.from_numpy(c))
    assert eng.base.capacity > 64          # grew past the initial granule
    full = Table.from_numpy({k: np.concatenate([c[k] for c in frames])
                             for k in frames[0]})
    off = cube.build_cuboid(full, specs, sorted(treatments), "y")
    assert _stat_map(eng.base) == _stat_map(off)
    # post-growth steady state: one dispatch again
    with count_dispatches() as n:
        eng.ingest(Table.from_numpy(frame(600, seed=99)))
    assert n() == 1


def test_bucketed_batch_padding_bounds_retraces():
    # an irregular stream (every batch a different row count) must NOT
    # trace the fused program once per size: batches pad to power-of-two
    # row buckets, so the trace count is bounded by log2(max batch)
    from repro.core.online import BATCH_BUCKET_GRANULE, _bucket_rows
    assert _bucket_rows(1) == BATCH_BUCKET_GRANULE
    assert _bucket_rows(BATCH_BUCKET_GRANULE) == BATCH_BUCKET_GRANULE
    assert _bucket_rows(BATCH_BUCKET_GRANULE + 1) == 2 * BATCH_BUCKET_GRANULE
    assert _bucket_rows(1000) == 1024

    # programs are cached module-wide per schema: start from a fresh one
    # so the trace count below belongs to THIS stream alone
    fused.get_fused_ingest.cache_clear()
    eng = OnlineEngine(SPECS, TREATMENTS, "y", granule=256)
    rng = np.random.default_rng(0)
    sizes = [int(s) for s in rng.integers(1, 1000, 24)]
    for i, sz in enumerate(sizes):
        cols, valid = _frame(sz, seed=200 + i)
        eng.ingest(Table.from_numpy(cols, valid))
    prog = eng._fused_program(False)
    # sizes in [1, 1000) span at most the 5 buckets {64,128,256,512,1024}
    n_buckets = len({_bucket_rows(s) for s in sizes})
    assert prog._cache_size() <= n_buckets <= 5, (
        prog._cache_size(), sorted(set(sizes)))
    # padding rows are invisible to the maintained state: same stream,
    # one engine fed exact-bucket batches, bit-identical stats
    ref = OnlineEngine(SPECS, TREATMENTS, "y", granule=256)
    for i, sz in enumerate(sizes):
        cols, valid = _frame(sz, seed=200 + i)
        pad = _bucket_rows(sz) - sz
        cols = {k: np.pad(v, (0, pad)) for k, v in cols.items()}
        ref.ingest(Table.from_numpy(cols, np.pad(valid, (0, pad))))
    assert _stat_map(eng.base) == _stat_map(ref.base)
    # reservoir state is bit-identical across PIPELINES too (all pad to
    # the same bucket before the streaming-propensity update)
    legacy = OnlineEngine(SPECS, TREATMENTS, "y", granule=256,
                          pipeline="planner")
    eng2 = OnlineEngine(SPECS, TREATMENTS, "y", granule=256)
    for i, sz in enumerate(sizes[:6]):
        cols, valid = _frame(sz, seed=200 + i)
        b = Table.from_numpy(cols, valid)
        legacy.ingest(b)
        eng2.ingest(b)
    np.testing.assert_array_equal(np.asarray(eng2.stream.priority),
                                  np.asarray(legacy.stream.priority))
    assert float(eng2.stream.n) == float(legacy.stream.n)


def test_touch_renormalization_before_int32_wraparound():
    eng = OnlineEngine(SPECS, TREATMENTS, "y", granule=256)
    feed = _batches(3, 300)
    for b in feed[:2]:
        eng.ingest(b)
    # fast-forward the stream to the renormalization threshold: shift the
    # counter AND every live stamp by the same offset (a legal state — it
    # is exactly what 2^31 - eps committed ingests would produce)
    shift = fused.TOUCH_RENORM_LIMIT + 5 - eng._ingest_count
    eng._ingest_count += shift
    eng._touch = {
        name: jnp.asarray(np.where(
            np.asarray(eng._view_table(name).group_valid),
            np.asarray(t) + shift, 0).astype(np.int32))
        for name, t in eng._touch.items()}
    assert eng._ingest_count >= fused.TOUCH_RENORM_LIMIT
    eng.ingest(feed[2])     # triggers the renormalization
    assert eng._ingest_count < fused.TOUCH_RENORM_LIMIT, \
        "counter was not renormalized"
    assert eng._ingest_count >= 0
    touch = np.asarray(eng._touch[BASE_VIEW])
    gv = np.asarray(eng.base.group_valid)
    assert touch[gv].min() >= 0
    assert touch[gv].max() <= eng._ingest_count
    # TTL semantics survive the shift: only the just-ingested batch's
    # groups survive ttl=0
    evicted = eng.evict(ttl=0)
    assert evicted[BASE_VIEW] >= 0
    survivors = np.asarray(eng._touch[BASE_VIEW])[
        np.asarray(eng.base.group_valid)]
    assert (survivors == eng._ingest_count).all()


def test_skewed_hash_distribution_keeps_partitions_under_capacity():
    # >90% of ROWS land in ONE partition's key range: mine the key space
    # for combos owned by partition 0 of 8 and concentrate the stream on
    # them. k-per-device partitioning must keep every partition's
    # occupancy within its (grown) capacity and stay exact.
    n_parts = 8
    codec = cube.make_codec(SPECS)
    combos = np.stack(np.meshgrid(np.arange(5), np.arange(4), np.arange(3),
                                  indexing="ij"), -1).reshape(-1, 3)
    hi, lo = codec.pack({"x0": jnp.asarray(combos[:, 0]),
                         "x1": jnp.asarray(combos[:, 1]),
                         "x2": jnp.asarray(combos[:, 2])},
                        jnp.ones((len(combos),), bool))
    pid = np.asarray(cube.partition_ids(np.asarray(hi), np.asarray(lo),
                                        n_parts))
    target = np.bincount(pid, minlength=n_parts).argmax()
    hot = combos[pid == target]
    cold = combos[pid != target]
    assert len(hot) >= 2

    rng = np.random.default_rng(3)
    n = 2000
    n_hot = int(n * 0.92)
    rows = np.concatenate([hot[rng.integers(0, len(hot), n_hot)],
                           cold[rng.integers(0, len(cold), n - n_hot)]])
    rng.shuffle(rows)
    cols = {"x0": rows[:, 0].astype(np.int32),
            "x1": rows[:, 1].astype(np.int32),
            "x2": rows[:, 2].astype(np.int32)}
    cols["ta"] = (rng.random(n) < 0.5).astype(np.int32)
    cols["tb"] = (rng.random(n) < 0.5).astype(np.int32)
    cols["y"] = np.round(rng.normal(0, 1, n)).astype(np.float32)

    ref = OnlineEngine(SPECS, TREATMENTS, "y", granule=64)
    eng = PartitionedOnlineEngine(SPECS, TREATMENTS, "y", granule=64,
                                  n_parts=n_parts)
    for s in range(0, n, 500):
        b = Table.from_numpy({k: v[s:s + 500] for k, v in cols.items()})
        ref.ingest(b)
        eng.ingest(b)
    # per-partition occupancy bounded by the per-partition capacity
    for name in (BASE_VIEW, *TREATMENTS):
        tab = eng._view_table(name)
        occ = np.asarray(tab.group_valid).sum(axis=1)
        assert occ.max() <= tab.capacity, (name, occ, tab.capacity)
        # the skew target partition really is hot
        assert occ.sum() > 0
    assert _stat_map(eng.base) == _stat_map(ref.base)
    for t in TREATMENTS:
        assert float(eng.ate(t).ate) == float(ref.ate(t).ate)
    # NOTE: capacity under skew is maintained by per-partition growth;
    # range REBALANCING (splitting hot ranges) is documented follow-up
    # work in ROADMAP.md.


def test_scatter_merge_parts_fused_kernel_matches_ref():
    from repro.kernels import ref
    from repro.kernels.ops import scatter_merge_parts_op
    rng = np.random.default_rng(9)
    p, c, s, b = 3, 256, 5, 130
    tables = rng.normal(0, 1, (p, c, s)).astype(np.float32)
    pos = rng.integers(0, c, (p, b)).astype(np.int32)
    vals = rng.normal(0, 1, (p, b, s)).astype(np.float32)
    got = scatter_merge_parts_op(jnp.asarray(tables), jnp.asarray(pos),
                                 jnp.asarray(vals), block=64)
    want = np.stack([np.asarray(ref.scatter_merge_ref(
        jnp.asarray(tables[i]), jnp.asarray(pos[i]), jnp.asarray(vals[i])))
        for i in range(p)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    # empty delta: no-op
    out = scatter_merge_parts_op(jnp.asarray(tables),
                                 jnp.zeros((p, 0), jnp.int32),
                                 jnp.zeros((p, 0, s), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), tables)


def test_use_pallas_fused_ingest_matches_default():
    a = OnlineEngine(SPECS, TREATMENTS, "y", granule=256)
    b = OnlineEngine(SPECS, TREATMENTS, "y", granule=256, use_pallas=True)
    pa = PartitionedOnlineEngine(SPECS, TREATMENTS, "y", granule=256,
                                 n_parts=2, use_pallas=True)
    for t in _batches(3, 400, seed0=50):
        a.ingest(t)
        b.ingest(t)
        pa.ingest(t)
    assert _stat_map(a.base) == _stat_map(b.base)
    assert _stat_map(a.base) == _stat_map(pa.base)
    for t in TREATMENTS:
        assert float(a.ate(t).ate) == float(b.ate(t).ate)
        assert float(a.ate(t).ate) == float(pa.ate(t).ate)


# ------------------------------------- re-sort slot map vs key search ----
WIDE = {"x0": CoarsenSpec.categorical(16), "x1": CoarsenSpec.categorical(8),
        "x2": CoarsenSpec.categorical(5)}


def _wide_batch(n, seed, x0_hi):
    rng = np.random.default_rng(seed)
    cols = {"x0": rng.integers(0, x0_hi, n).astype(np.int32),
            "x1": rng.integers(0, 8, n).astype(np.int32),
            "x2": rng.integers(0, 5, n).astype(np.int32),
            "ta": (rng.random(n) < 0.5).astype(np.int32),
            "tb": (rng.random(n) < 0.3).astype(np.int32)}
    cols["y"] = np.round(rng.normal(0, 2, n)).astype(np.float32)
    return Table.from_numpy(cols, rng.random(n) > 0.1)


# new keys, re-touched and untouched old keys, invalid rows and padded
# slots; the fourth batch outgrows both the views' and the delta's
# capacity, so its first dispatches pass the state through
SLOT_FEED = [(200, 1, 2), (150, 2, 3), (120, 3, 3), (400, 4, 16),
             (100, 5, 16)]


def _resort_merge_by_search(hi, lo, stats, gv, touch, d_hi, d_lo, d_stats,
                            d_gv, counter):
    """Reference re-sort merge that finds slots by key search: the new
    slot of every old key and every delta key binary-searched in the
    merged table (``remap_touch``, ``lookup_rows_in_table``)."""
    cap = hi.shape[0]
    g = groupby.group_by_key(jnp.concatenate([hi, d_hi]),
                             jnp.concatenate([lo, d_lo]))
    sums = groupby.segment_sums(
        g, {k: jnp.concatenate([stats[k], d_stats[k]]) for k in stats})
    nhi, nlo = g.group_hi[:cap], g.group_lo[:cap]
    pos, _ = groupby.lookup_rows_in_table(d_hi, d_lo, nhi, nlo)
    moved = fused.remap_touch(hi, lo, gv, nhi, nlo, touch)
    return (nhi, nlo, {k: v[:cap] for k, v in sums.items()},
            g.group_valid[:cap], fused.stamp_touch(moved, pos, d_gv, counter),
            g.n_groups)


def _clear_ingest_programs():
    fused.get_fused_ingest.cache_clear()
    fused.get_fused_ingest_parts.cache_clear()


def _assert_bit_identical(got, want):
    a = jax.tree_util.tree_leaves_with_path(got)
    b = jax.tree_util.tree_leaves_with_path(want)
    assert [p for p, _ in a] == [p for p, _ in b]
    for (path, x), (_, y) in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, path
        assert x.tobytes() == y.tobytes(), path


def _uncommitted_dispatch(eng, batch):
    """Run the engine's ingest program on ``batch`` without donating or
    committing: (commit verdict, output views, input views)."""
    padded = eng._bucket_pad(batch)
    before = jax.device_get(eng._pack_view_state()["views"])
    state, verdicts = eng._fused_program(False, donate=False)(
        {c: padded.columns[c] for c in eng._row_cols}, padded.valid,
        eng._pack_view_state(), jnp.int32(eng._ingest_count + 1),
        jnp.int32(eng.stream.n_batches))
    return bool(verdicts["commit"]), jax.device_get(state["views"]), before


@pytest.mark.parametrize("make", [
    lambda: OnlineEngine(WIDE, TREATMENTS, "y", granule=64,
                         delta_granule=128),
    lambda: PartitionedOnlineEngine(WIDE, TREATMENTS, "y", granule=64,
                                    delta_granule=128, n_parts=2),
], ids=["replicated", "partitioned"])
def test_resort_slot_map_matches_key_search_bit_for_bit(make, monkeypatch):
    """Committed keys, stats, group flags and touch stamps of every view,
    after every batch, equal those of the same program whose re-sort
    merge binary-searches each key, bit for bit."""
    feed = [_wide_batch(*args) for args in SLOT_FEED]

    def run(check_pass_through):
        _clear_ingest_programs()
        eng = make()
        states, held = [], 0
        for b in feed:
            if check_pass_through:
                commit, out, before = _uncommitted_dispatch(eng, b)
                if not commit:
                    held += 1
                    _assert_bit_identical(out, before)
            rep = eng.ingest(b)
            assert not all(rep.fast_path.values())
            states.append(jax.device_get(eng._pack_view_state()["views"]))
        return states, held

    try:
        got, held = run(check_pass_through=True)
        traced = []

        def by_search(*args):
            traced.append(True)
            return _resort_merge_by_search(*args)

        with monkeypatch.context() as m:
            m.setattr(fused, "_resort_merge", by_search)
            want, _ = run(check_pass_through=False)
    finally:
        _clear_ingest_programs()
    assert traced and held >= 1
    assert got[-1][BASE_VIEW]["hi"].shape[-1] > got[0][BASE_VIEW][
        "hi"].shape[-1]                                # a capacity grew
    touch = got[-1][BASE_VIEW]["touch"]
    assert len(np.unique(touch[got[-1][BASE_VIEW]["gv"]])) > 2
    for g, w in zip(got, want):
        _assert_bit_identical(g, w)


# --------------------------- k partitions per device (mesh, subprocess) ----
def _run_subprocess(body: str):
    code = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=900,
                          env={**os.environ, "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


def test_k_partitions_per_device_bit_identical_on_mesh():
    out = _run_subprocess("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    assert jax.device_count() == 4
    from repro.core import CoarsenSpec, OnlineEngine, PartitionedOnlineEngine
    from repro.data.columnar import Table
    from repro.launch.mesh import make_data_mesh

    SPECS = {"x0": CoarsenSpec.categorical(5),
             "x1": CoarsenSpec.categorical(4),
             "x2": CoarsenSpec.categorical(3)}
    TREATMENTS = {"ta": ["x0", "x1"], "tb": ["x0", "x2"]}

    def frame(n, seed, x0_hi=5):
        rng = np.random.default_rng(seed)
        cols = {"x0": rng.integers(0, x0_hi, n).astype(np.int32),
                "x1": rng.integers(0, 4, n).astype(np.int32),
                "x2": rng.integers(0, 3, n).astype(np.int32)}
        cols["ta"] = (rng.random(n) < 0.2 + 0.5 * cols["x0"] / 4
                      ).astype(np.int32)
        cols["tb"] = (rng.random(n) < 0.4).astype(np.int32)
        cols["y"] = np.round(2.0 * cols["ta"] + 1.5 * cols["x0"]
                             + rng.normal(0, 0.5, n)).astype(np.float32)
        return cols, rng.random(n) > 0.08

    def stat_map(cub):
        gv = (np.asarray(cub.group_valid)
              & (np.asarray(cub.stats["one"]) != 0)).reshape(-1)
        hi = np.asarray(cub.key_hi).reshape(-1)[gv]
        lo = np.asarray(cub.key_lo).reshape(-1)[gv]
        c = {k: np.asarray(v).reshape(-1)[gv]
             for k, v in sorted(cub.stats.items())}
        return {(int(h), int(l)): tuple(float(c[k][i]) for k in c)
                for i, (h, l) in enumerate(zip(hi, lo))}

    mesh = make_data_mesh(4)
    c1, v1 = frame(3000, seed=1, x0_hi=2)
    c2, v2 = frame(2024, seed=2)
    cols = {k: np.concatenate([c1[k], c2[k]]) for k in c1}
    valid = np.concatenate([v1, v2])
    ref = OnlineEngine(SPECS, TREATMENTS, "y", granule=256)
    sharded = OnlineEngine(SPECS, TREATMENTS, "y", granule=256, mesh=mesh)
    # k = 2 and k = 3 partitions per device
    engines = {8: PartitionedOnlineEngine(SPECS, TREATMENTS, "y",
                                          granule=256, mesh=mesh,
                                          n_parts=8),
               12: PartitionedOnlineEngine(SPECS, TREATMENTS, "y",
                                           granule=256, mesh=mesh,
                                           n_parts=12)}
    s = 0
    # 999/1001 exercise the in-program batch padding (not % 4 == 0)
    for sz in [999, 1001, 1000, 1000, 1024]:
        b = Table.from_numpy({k: v[s:s + sz] for k, v in cols.items()},
                             valid[s:s + sz])
        r0 = ref.ingest(b)
        sharded.ingest(b)
        for np_, eng in engines.items():
            r = eng.ingest(b)
            assert r.n_delta_groups == r0.n_delta_groups, np_
        s += sz
    full = Table.from_numpy(cols, valid)
    import jax.sharding as shd
    # streaming-propensity state must cover the FULL batch on a mesh
    # (regression: the fused shard_map body once updated the reservoir
    # from the local row shard only), bit-identically to the no-mesh ref
    for label, eng in (("sharded", sharded),
                       *((n, e) for n, e in engines.items())):
        assert float(eng.stream.n) == float(ref.stream.n), label
        for c in ref.stream.names:
            assert float(eng.stream.sums[c]) == float(ref.stream.sums[c]), \
                (label, c)
        np.testing.assert_array_equal(np.asarray(eng.stream.priority),
                                      np.asarray(ref.stream.priority),
                                      err_msg=str(label))
    for np_, eng in engines.items():
        assert stat_map(eng.base) == stat_map(ref.base), np_
        assert isinstance(eng.base.key_hi.sharding, shd.NamedSharding)
        assert eng.base.key_hi.shape[0] == np_
        for t in TREATMENTS:
            cub, _ = eng._view_state(t)
            assert stat_map(cub) == stat_map(ref.views[t].cuboid), (np_, t)
            assert float(eng.ate(t).ate) == float(ref.ate(t).ate)
            assert float(eng.ate(t).variance) == float(ref.ate(t).variance)
            np.testing.assert_array_equal(
                np.asarray(eng.matched_rows(t, full)),
                np.asarray(ref.matched_rows(t, full)))
        # per-device resident state is ~1/4 of the total (k rows/device)
        sb = eng.state_bytes()
        assert sb["per_device"] * 4 <= sb["total"] * 1.01, (np_, sb)
    # n_parts not a multiple of the device count is rejected
    try:
        PartitionedOnlineEngine(SPECS, TREATMENTS, "y", mesh=mesh,
                                n_parts=6)
        raise SystemExit("n_parts=6 on 4 devices was not rejected")
    except ValueError:
        pass
    print("K_PER_DEVICE_OK")
    """)
    assert "K_PER_DEVICE_OK" in out
