"""Online engine: per-batch delta maintenance vs full offline recompute.

The claim under measurement (paper's online setting): once a base table is
materialized, folding a small streamed batch in and re-answering the causal
query costs O(batch + stat-table capacity) — asymptotically below the
offline path, which re-coarsens/re-groups ALL rows per refresh. Since the
fused single-dispatch pipeline, the second claim is DISPATCH cost: the
steady-state ingest is ONE compiled program launch (state donated in
place), vs the PR 3 planner's build+plan+commit launches.

All rows are median-of-5 after 2 warmup iterations (warmup also settles
capacity growth and jit traces), so fused-vs-planner deltas are stable.

Emits, per batch size B:
  online_ingest_bB            fold one B-row batch into every view —
                              fused single-dispatch pipeline (default)
  online_ingest_planner_bB    same stream, PR 3 two-dispatch planner path
  online_ingest_unfused_bB    same, legacy one-blocking-sync-per-merge loop
  online_query_bB             uncached ATE from materialized state (fused
                              one-dispatch query pipeline)
  online_cached_query_bB      repeat ATE (estimate cache hit: 0 dispatches)
  offline_recompute_bB        full CEM + ATE over the N+B-row table
plus dispatch-count rows (jit-launch counter, repro.launch.trace):
  online_dispatches_*         compiled launches per steady-state ingest,
                              fused1 vs planner vs unfused
  online_query_dispatches_*   compiled launches per UNCACHED ate() on the
                              partitioned engine, fused (=1) vs the
                              assemble host-path baseline (reassembly +
                              estimate)
and, per device count D (every mesh size up to the devices this process
sees; on CPU force them with --xla_force_host_platform_device_count):
  online_ingest_fused1_dD         fused single-dispatch, replicated views
  online_ingest_fused1_part_dD    fused single-dispatch, partitioned views
  online_ingest_dD                planner path, replicated views
  online_ingest_part_dD           planner path, partitioned views
  online_query_fused_dD           uncached fused ate() on the partitioned
                                  engine (per-device masking ~1/D)
  online_rowlookup_part_dD        fused matched_rows probe (routed lookup
                                  on a mesh) on the partitioned engine
  online_state_bytes_dD           per-device resident bytes, partitioned
                                  (must show ~1/D scaling)
  online_state_bytes_replicated_dD  same accounting on the replicated
                              engine, so memory claims are comparable

Serving rows (batched heterogeneous-spec query path, PR 6):
  online_serve_qps_bB         B distinct uncached subpopulation queries
                              answered as ONE batched dispatch; value
                              slot = seconds PER QUERY (wave latency / B)
                              so the guard trips when batching stops
                              amortizing; qps rides in the derived field
  online_serve_p50 / _p99     per-query latency under Poisson arrivals
                              through the ServingEngine continuous
                              batcher (completion - arrival)

MVCC overlap rows (PR 8): sustained ingest under a fixed query cadence
(a dashboard wave of 8 subpopulation specs re-queried after EVERY batch,
commit every max_inflight batches). overlap=True dispatches the ingest
without syncing and serves waves from the stable committed snapshot, so
between commits the estimate cache stays valid and most waves never
touch the device; the stop-the-world baseline blocks on each batch's
verdict and invalidates touched cache entries per ingest:
  online_overlap_ingest_serve       seconds per round (k batches + k
                                    waves + commit), overlap=True;
                                    rows/sec, speedup, cache-hit
                                    fraction ride the derived field
  online_overlap_interleave_baseline  same round, synchronous pipeline

REPRO_BENCH_SMOKE=1 shrinks N for CI smoke runs (full mode: N = 2^20).
"""
import sys
import time

import numpy as np

from benchmarks.common import emit, smoke, timeit
from repro.core import (CoarsenSpec, OnlineEngine, PartitionedOnlineEngine,
                        cem, estimate_ate)
from repro.data.columnar import Table

SPECS = {"x0": CoarsenSpec.categorical(8), "x1": CoarsenSpec.categorical(6),
         "x2": CoarsenSpec.categorical(5)}
TREATMENTS = {"t": ["x0", "x1", "x2"]}

WARMUP, ITERS = 2, 5     # median-of-5 per row; warmup settles traces


def _gen(n, seed):
    rng = np.random.default_rng(seed)
    cols = {
        "x0": rng.integers(0, 8, n).astype(np.int32),
        "x1": rng.integers(0, 6, n).astype(np.int32),
        "x2": rng.integers(0, 5, n).astype(np.int32),
    }
    p = 0.15 + 0.6 * cols["x0"] / 7
    cols["t"] = (rng.random(n) < p).astype(np.int32)
    cols["y"] = (2.0 * cols["t"] + 1.5 * cols["x0"]
                 + rng.normal(0, 0.5, n)).astype(np.float32)
    return cols


def _mixed_subpops(n, seed=0):
    """n DISTINCT subpopulation predicates over the bench schema (random
    per-dim bucket subsets). Distinctness matters: ``ate_batch`` collapses
    duplicate in-flight specs onto one slot, so a batch of repeats would
    measure a smaller dispatch than the row name claims."""
    rng = np.random.default_rng(seed)
    dims = [("x0", 8), ("x1", 6), ("x2", 5)]
    out, seen = [], set()
    while len(out) < n:
        sub = {}
        for d, card in dims:
            if rng.random() < 0.6:
                k = int(rng.integers(1, card))
                sub[d] = sorted(int(v) for v in
                                rng.choice(card, size=k, replace=False))
        key = tuple((d, tuple(v)) for d, v in sorted(sub.items()))
        if not sub or key in seen:
            continue
        seen.add(key)
        out.append(sub)
    return out


def _ingest_latency(eng, bs, seed0):
    """Median ingest latency over ITERS distinct batches (after WARMUP
    distinct batches): re-ingesting identical rows would let every repeat
    hit the warm fast path artificially."""
    feed = [_gen(bs, seed=seed0 + i) for i in range(WARMUP + ITERS)]
    batches = iter([Table.from_numpy(c) for c in feed])
    t, _ = timeit(lambda: eng.ingest(next(batches)),
                  warmup=WARMUP, iters=ITERS)
    return t, feed


def _steady_dispatches(eng, bs, seed0):
    """Compiled launches of one steady-state ingest (trace counter)."""
    from repro.launch.trace import count_dispatches
    eng.ingest(Table.from_numpy(_gen(bs, seed=seed0)))   # settle shapes
    with count_dispatches() as n:
        eng.ingest(Table.from_numpy(_gen(bs, seed=seed0 + 1)))
    return n()


def _sweep_one(ndev: int, n: int, bs: int, warmup: int, iters: int):
    """One mesh size of :func:`sharded_sweep`, in this process: the four
    engine variants' median ingest seconds and resident bytes, then the
    partitioned fused engine's uncached query and row lookup."""
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh(ndev) if ndev > 1 else None
    res, engines = {}, {}
    for label, cls, kw in (
            ("fused1", OnlineEngine, dict()),
            ("fused1_part", PartitionedOnlineEngine,
             dict(n_parts=None if ndev > 1 else 1)),
            ("replicated", OnlineEngine, dict(pipeline="planner")),
            ("partitioned", PartitionedOnlineEngine,
             dict(pipeline="planner", n_parts=None if ndev > 1 else 1))):
        eng = cls.from_table(Table.from_numpy(_gen(n, seed=0)),
                             SPECS, TREATMENTS, "y", mesh=mesh, **kw)
        engines[label] = eng
        feed = [Table.from_numpy(_gen(bs, seed=1 + i))
                for i in range(warmup + iters)]
        for b in feed[:warmup]:
            eng.ingest(b)
        ts = []
        for b in feed[warmup:]:
            t0 = time.perf_counter()
            eng.ingest(b)
            ts.append(time.perf_counter() - t0)
        res[label] = dict(secs=float(np.median(ts)), **eng.state_bytes())
    # device-resident query pipeline on the partitioned fused engine:
    # uncached fused ate() (one dispatch + one scalar fetch) and the fused
    # row-lookup probe (routed over the mesh when ndev > 1)
    qeng = engines["fused1_part"]
    probe = Table.from_numpy(_gen(4096, seed=777))
    for _ in range(warmup):
        qeng._cache.clear()
        qeng.ate("t")
        qeng.matched_rows("t", probe).block_until_ready()
    ts = []
    for _ in range(iters):
        qeng._cache.clear()
        t0 = time.perf_counter()
        qeng.ate("t")
        ts.append(time.perf_counter() - t0)
    res["query_fused_part"] = dict(secs=float(np.median(ts)))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        qeng.matched_rows("t", probe).block_until_ready()
        ts.append(time.perf_counter() - t0)
    res["rowlookup_part"] = dict(secs=float(np.median(ts)))
    return res


def sharded_sweep(n: int, bs: int, device_counts, warmup=WARMUP,
                  iters=ITERS):
    """Per-batch ingest latency + per-device resident state per data-mesh
    size: fused single-dispatch vs planner, replicated vs partitioned
    views. Every mesh is built in THIS process from the devices it
    already sees (one process holds the chip); a CPU caller forces host
    devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=D``
    before start. A failed mesh size raises."""
    for ndev in device_counts:
        res = _sweep_one(ndev, n, bs, warmup, iters)
        rep, part = res["replicated"], res["partitioned"]
        f1, f1p = res["fused1"], res["fused1_part"]
        emit(f"online_ingest_fused1_d{ndev}", f1["secs"],
             f"n={n} batch={bs} vs_planner="
             f"{rep['secs'] / max(f1['secs'], 1e-12):.2f}x")
        emit(f"online_ingest_fused1_part_d{ndev}", f1p["secs"],
             f"n={n} batch={bs} vs_planner="
             f"{part['secs'] / max(f1p['secs'], 1e-12):.2f}x")
        emit(f"online_ingest_d{ndev}", rep["secs"], f"n={n} batch={bs}")
        emit(f"online_ingest_part_d{ndev}", part["secs"],
             f"n={n} batch={bs} vs_replicated="
             f"{part['secs'] / max(rep['secs'], 1e-12):.2f}x")
        emit(f"online_query_fused_d{ndev}", res["query_fused_part"]["secs"],
             f"n={n} uncached fused ate() on partitioned views "
             f"(1 dispatch + 1 scalar fetch)")
        emit(f"online_rowlookup_part_d{ndev}",
             res["rowlookup_part"]["secs"],
             "fused matched_rows, 4096 probe rows "
             f"({'routed all-to-all' if ndev > 1 else 'partition-local'})")
        # state scaling rows: seconds slot carries no latency — emit 0-cost
        # with the bytes in the derived column (JSON artifact keeps both)
        emit(f"online_state_bytes_d{ndev}", 0.0,
             f"replicated_per_device={rep['per_device']} "
             f"partitioned_per_device={part['per_device']} "
             f"partitioned_total={part['total']} "
             f"shrink={rep['per_device'] / max(part['per_device'], 1):.2f}x")
        emit(f"online_state_bytes_replicated_d{ndev}", 0.0,
             f"total={rep['total']} per_device={rep['per_device']} "
             f"fused1_total={f1['total']} "
             f"fused1_per_device={f1['per_device']}")


def main() -> None:
    n = 1 << 16 if smoke() else 1 << 20
    batch_sizes = [256, 4096] if smoke() else [256, 4096, 65536]
    base_cols = _gen(n, seed=0)
    base = Table.from_numpy(base_cols)

    eng = OnlineEngine.from_table(base, SPECS, TREATMENTS, "y")
    planner = OnlineEngine.from_table(base, SPECS, TREATMENTS, "y",
                                      pipeline="planner")
    legacy = OnlineEngine.from_table(base, SPECS, TREATMENTS, "y",
                                     pipeline="unfused")
    ingested = [base_cols]
    for bs in batch_sizes:
        t_ing, feed = _ingest_latency(eng, bs, seed0=bs)
        ingested += feed
        emit(f"online_ingest_b{bs}", t_ing,
             f"n={n} views={len(eng.views) + 1} pipeline=fused1")

        # the same stream through the PR 3 planner and the legacy
        # per-merge-host-sync loop: deltas vs the fused single dispatch
        # are dispatch/serialization cost
        t_plan, _ = _ingest_latency(planner, bs, seed0=1_000_000 + bs)
        emit(f"online_ingest_planner_b{bs}", t_plan,
             f"fused1_speedup={t_plan / max(t_ing, 1e-12):.2f}x "
             f"fused1_saves={(t_plan - t_ing) * 1e3:.2f}ms")
        t_unf, _ = _ingest_latency(legacy, bs, seed0=2_000_000 + bs)
        emit(f"online_ingest_unfused_b{bs}", t_unf,
             f"fused1_speedup={t_unf / max(t_ing, 1e-12):.2f}x")

        def query():
            eng._cache.clear()
            return eng.ate("t")
        t_q, _ = timeit(query, warmup=WARMUP, iters=ITERS)
        emit(f"online_query_b{bs}", t_q,
             f"groups={int(eng.views['t'].cuboid.n_groups())}")

        t_cq, _ = timeit(lambda: eng.ate("t"), warmup=WARMUP, iters=ITERS)
        emit(f"online_cached_query_b{bs}", t_cq, "")

        # offline recompute over the SAME rows the engine now holds
        full = Table.from_numpy(
            {k: np.concatenate([c[k] for c in ingested])
             for k in base_cols})

        def offline():
            return estimate_ate(cem(full, "t", "y", SPECS).groups)
        t_off, _ = timeit(offline, warmup=WARMUP, iters=ITERS)
        speedup = t_off / max(t_ing + t_q, 1e-12)
        emit(f"offline_recompute_b{bs}", t_off,
             f"online_speedup={speedup:.1f}x")

    # dispatch-count rows: compiled launches per steady-state ingest. The
    # COUNT rides in the value slot (1 count == 1 "us") so the CI
    # regression guard (tools/check_bench.py, 1.5x) actually fails when
    # the fused pipeline regresses from one dispatch — a free-text
    # derived field would never trip it.
    d_f = _steady_dispatches(eng, batch_sizes[0], seed0=42)
    d_p = _steady_dispatches(planner, batch_sizes[0], seed0=52)
    d_u = _steady_dispatches(legacy, batch_sizes[0], seed0=62)
    for name, d in (("fused1", d_f), ("planner", d_p), ("unfused", d_u)):
        emit(f"online_dispatches_{name}", d / 1e6,
             "compiled launches per steady ingest (value slot = count)")

    # query dispatch-count rows: uncached ate() on the PARTITIONED engine,
    # fused one-dispatch pipeline vs the assemble host-path baseline
    # (canonical reassembly + estimate). Same value-slot convention.
    from repro.launch.trace import count_dispatches
    part = PartitionedOnlineEngine.from_table(
        Table.from_numpy(_gen(1 << 14 if smoke() else 1 << 16, seed=7)),
        SPECS, TREATMENTS, "y", n_parts=4)
    part.ate("t")
    part._estimate("t", None, pipeline="assemble")      # warm both paths
    part._cache.clear()
    with count_dispatches() as nq:
        part.ate("t")
    d_qf = nq()
    part._assembled.clear()                             # cold reassembly
    with count_dispatches() as nq:
        part._estimate("t", None, pipeline="assemble")
    d_qa = nq()
    for name, d in (("fused", d_qf), ("assemble", d_qa)):
        emit(f"online_query_dispatches_{name}", d / 1e6,
             "compiled launches per uncached ate() (value slot = count)")

    # serving rows: B DISTINCT uncached subpopulation queries as ONE
    # batched dispatch (cache cleared per iteration so the batched
    # program really computes). Value slot = seconds per query so the
    # 1.5x guard catches the batch path losing its amortization.
    from repro.core.serving import ServingEngine, run_poisson_load
    for bsz in (1, 32, 256):
        specs = [("t", s) for s in _mixed_subpops(bsz, seed=bsz)]

        def batch_query():
            eng._cache.clear()
            return eng.ate_batch(specs)
        t_b, _ = timeit(batch_query, warmup=WARMUP, iters=ITERS)
        emit(f"online_serve_qps_b{bsz}", t_b / bsz,
             f"qps={bsz / max(t_b, 1e-12):.0f} wave_secs={t_b:.4f} "
             f"(one dispatch, {bsz} distinct subpopulations)")

    # Poisson arrival load through the continuous batcher: per-query
    # latency percentiles (completion - arrival). Rate is set well below
    # the single-wave ceiling so the queue stays stable and p99 measures
    # batching jitter, not saturation.
    n_load = 64 if smoke() else 512
    load_specs = [("t", s) for s in _mixed_subpops(n_load, seed=99)]
    srv = ServingEngine(eng, n_slots=32)
    # warm every pow2 wave bucket the batcher can produce — otherwise the
    # percentiles measure trace time, not serving latency
    for b in (1, 2, 4, 8, 16, 32):
        eng._cache.clear()
        eng.ate_batch(load_specs[:b])
    eng._cache.clear()
    lat = run_poisson_load(srv, load_specs, rate_qps=200.0, seed=0)
    emit("online_serve_p50", float(np.percentile(lat, 50)),
         f"poisson 200qps n={n_load} slots=32 waves={srv.n_waves}")
    emit("online_serve_p99", float(np.percentile(lat, 99)),
         f"poisson 200qps n={n_load} slots=32")

    # MVCC overlap rows: sustained ingest WHILE a ServingEngine answers a
    # fixed query cadence (an 8-spec dashboard wave after EVERY batch).
    # overlap=True only dispatches each ingest — waves serve the stable
    # committed snapshot, so between commits (every max_inflight batches)
    # the estimate cache stays VALID and waves are host-side cache hits;
    # verdicts are fetched once per commit. The stop-the-world baseline
    # blocks on every batch's verdict AND invalidates the touched cache
    # entries per ingest, so every wave re-dispatches.
    from repro.launch.trace import count_host_syncs
    bs_ov, k_commit = 4096, 4
    n_rounds = 4 if smoke() else 8       # one round = k_commit batches
    ov_specs = [("t", s) for s in _mixed_subpops(8, seed=5)]
    ov_base = Table.from_numpy(_gen(1 << 14 if smoke() else 1 << 16,
                                    seed=3))

    def overlap_round_secs(overlap: bool):
        kw = dict(overlap=True, max_inflight=k_commit) if overlap else {}
        e = OnlineEngine.from_table(ov_base, SPECS, TREATMENTS, "y", **kw)
        srv = ServingEngine(e, n_slots=8)
        feed = [Table.from_numpy(_gen(bs_ov, seed=3000 + i))
                for i in range(k_commit * (WARMUP + n_rounds))]
        it = iter(feed)

        def round_():
            for _ in range(k_commit):
                e.ingest(next(it))
                for q in ov_specs:
                    srv.submit(q)
                srv.step()
            if overlap:
                e.commit()
        for _ in range(WARMUP):          # settle traces, caps, cache
            round_()
        with count_host_syncs() as syncs:
            ts = []
            for _ in range(n_rounds):
                t0 = time.perf_counter()
                round_()
                ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), syncs() / n_rounds, srv
    t_ov, s_ov, srv_ov = overlap_round_secs(True)
    t_sw, s_sw, srv_sw = overlap_round_secs(False)
    rows = bs_ov * k_commit              # per round
    emit("online_overlap_ingest_serve", t_ov,
         f"rows_per_sec={rows / max(t_ov, 1e-12):.0f} "
         f"vs_interleave={t_sw / max(t_ov, 1e-12):.2f}x "
         f"syncs_per_round={s_ov:.2f} cache_served="
         f"{srv_ov.n_cache_served}/{srv_ov.n_served} "
         f"waves={srv_ov.n_waves} requeued={srv_ov.n_requeued} "
         f"(round = {k_commit} x {bs_ov}-row batches + "
         f"{len(ov_specs)}-spec wave each, commit per round)")
    emit("online_overlap_interleave_baseline", t_sw,
         f"rows_per_sec={rows / max(t_sw, 1e-12):.0f} "
         f"syncs_per_round={s_sw:.2f} cache_served="
         f"{srv_sw.n_cache_served}/{srv_sw.n_served} "
         f"waves={srv_sw.n_waves} (stop-the-world: per-batch verdict "
         "fetch + per-batch cache invalidation)")

    # durability rows (PR 9): WAL journaling overhead on the steady-state
    # ingest and cold crash recovery (newest checkpoint restore +
    # in-order WAL-tail replay). Both overhead rows use the
    # value-slot-=-ratio convention so the 1.5x guard trips when
    # journaling stops being cheap. The CONTRACT row (< 1.15x) is the
    # overlap configuration — the same steady-state regime every other
    # claim in this file measures, where the fsync rides the commit
    # barrier and amortizes over max_inflight batches; the _sync row is
    # the per-record-fsync synchronous pipeline, which pays a full disk
    # barrier per batch by design (informational).
    import shutil
    import tempfile

    from repro.core import DurableEngine
    bs_wal, k_wal = 4096, 8
    wal_n = 1 << 14 if smoke() else 1 << 16
    wal_base = Table.from_numpy(_gen(wal_n, seed=17))

    def wal_round_secs(durable: bool, rounds: int = 8):
        e = OnlineEngine.from_table(wal_base, SPECS, TREATMENTS, "y",
                                    overlap=True, max_inflight=k_wal)
        d = tempfile.mkdtemp(prefix="bench_wal_") if durable else None
        eng = DurableEngine(e, d) if durable else e
        feed = iter([Table.from_numpy(_gen(bs_wal, seed=7_000_000 + i))
                     for i in range(k_wal * (WARMUP + rounds))])

        def round_():
            for _ in range(k_wal):
                eng.ingest(next(feed))
            eng.commit()
        try:
            for _ in range(WARMUP):
                round_()
            ts = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                round_()
                ts.append(time.perf_counter() - t0)
        finally:
            if durable:
                eng.close()
                shutil.rmtree(d, ignore_errors=True)
        return float(np.median(ts)) / k_wal
    t_wplain = wal_round_secs(False)
    t_wdur = wal_round_secs(True)
    emit("online_wal_overhead", (t_wdur / max(t_wplain, 1e-12)) / 1e6,
         f"durable={t_wdur * 1e3:.2f}ms plain={t_wplain * 1e3:.2f}ms "
         f"per batch={bs_wal}, overlap commit every {k_wal} "
         f"(value slot = ratio, contract < 1.15)")

    plain = OnlineEngine.from_table(wal_base, SPECS, TREATMENTS, "y")
    t_plain, _ = _ingest_latency(plain, bs_wal, seed0=4_000_000)
    wal_dir = tempfile.mkdtemp(prefix="bench_wal_")
    try:
        dur = DurableEngine(
            OnlineEngine.from_table(wal_base, SPECS, TREATMENTS, "y"),
            wal_dir)
        t_dur, _ = _ingest_latency(dur, bs_wal, seed0=5_000_000)
        emit("online_wal_overhead_sync",
             (t_dur / max(t_plain, 1e-12)) / 1e6,
             f"durable={t_dur * 1e3:.2f}ms plain={t_plain * 1e3:.2f}ms "
             f"batch={bs_wal} fsync-per-record (value slot = ratio)")
        # recovery: a checkpoint plus a 3-batch WAL tail on disk, then
        # rebuild a FRESH engine from that state (restore + replay)
        dur.checkpoint(wait=True)
        n_tail = 3
        for i in range(n_tail):
            dur.ingest(Table.from_numpy(_gen(bs_wal, seed=6_000_000 + i)))
        dur.commit()
        dur.close()

        def recover():
            d = DurableEngine.recover(
                OnlineEngine(SPECS, TREATMENTS, "y"), wal_dir)
            d.close()
            return d
        t_rec, _ = timeit(recover, warmup=1, iters=3)
        emit("online_recover_secs", t_rec,
             f"ckpt(n={wal_n}+{WARMUP + ITERS}x{bs_wal}) + "
             f"{n_tail}-record WAL tail replay, cold engine")
    finally:
        shutil.rmtree(wal_dir, ignore_errors=True)

    # replication rows (PR 10): WAL shipping is pure host bytes off the
    # primary's write path, so the primary's steady-state ingest+commit
    # must stay within 1.10x of an unreplicated durable engine while a
    # follower is shipped every commit. The TIMED region is the primary's
    # ingest+commit only; ship/apply run in the same loop untimed — they
    # are follower-side cost (journal fsync + replay dispatch) that a
    # real deployment pays on the follower's disk, but their interleaving
    # (tail reads of the live log, page-cache pressure) is exactly what
    # could slow the primary down. Ratio in the value slot, same
    # convention as the WAL overhead rows; a kept-up follower's lag pins
    # at 0 seqs; failover is kill -> promote -> first answer.
    from repro.core import ReplicatedEngine

    def repl_round_secs(replicated: bool, rounds: int = 8):
        d = tempfile.mkdtemp(prefix="bench_repl_")
        engines = [OnlineEngine.from_table(wal_base, SPECS, TREATMENTS,
                                           "y", overlap=True,
                                           max_inflight=k_wal)]
        if replicated:
            engines.append(OnlineEngine(SPECS, TREATMENTS, "y"))
        cluster = ReplicatedEngine(engines, d, heartbeat_timeout_s=1e9)
        feed = iter([Table.from_numpy(_gen(bs_wal, seed=8_000_000 + i))
                     for i in range(k_wal * (WARMUP + rounds))])

        def round_():
            t0 = time.perf_counter()
            for _ in range(k_wal):
                cluster.ingest(next(feed))
            cluster.commit()
            dt = time.perf_counter() - t0
            cluster.ship()                  # untimed follower-side work
            cluster.apply_all()
            return dt
        try:
            for _ in range(WARMUP):
                round_()
            ts = [round_() for _ in range(rounds)]
            lag = max((r.replica_lag
                       for r in cluster.replicas.values()), default=0)
            return float(np.median(ts)) / k_wal, lag, cluster, d
        except BaseException:
            shutil.rmtree(d, ignore_errors=True)
            raise

    t_solo, _, solo, solo_dir = repl_round_secs(False)
    solo.primary.close()
    shutil.rmtree(solo_dir, ignore_errors=True)
    t_repl, lag, cluster, repl_dir = repl_round_secs(True)
    try:
        emit("online_primary_ship_overhead",
             (t_repl / max(t_solo, 1e-12)) / 1e6,
             f"shipping={t_repl * 1e3:.2f}ms solo={t_solo * 1e3:.2f}ms "
             f"per batch={bs_wal}, 1 follower shipped+applied every "
             f"{k_wal} (value slot = ratio, contract < 1.10)")
        emit("online_replica_lag", lag / 1e6,
             f"applied-vs-primary seqs after a tick "
             f"(value slot = seqs, contract = 0: the follower keeps up)")
        # failover: primary dies, most-caught-up follower is fenced-in,
        # drained, re-opened as primary, and answers its first query
        t0 = time.perf_counter()
        cluster.kill_primary()
        cluster.failover()
        cluster.ate("t")
        t_fo = time.perf_counter() - t0
        emit("online_failover_secs", t_fo,
             f"kill -> promote (epoch CAS + drain + reopen) -> first "
             f"answer; follower was {lag} seqs behind")
        cluster.primary.close()
    finally:
        shutil.rmtree(repl_dir, ignore_errors=True)

    # sharded ingest: per-batch latency per device-mesh size, over the
    # devices this process sees
    import jax
    sweep_n = 1 << 15 if smoke() else 1 << 18
    device_counts = [d for d in ((1, 2) if smoke() else (1, 2, 4, 8))
                     if d <= len(jax.devices())]
    sharded_sweep(sweep_n, 4096, device_counts)


if __name__ == "__main__":
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    main()
