"""Device-resident query pipeline: one compiled dispatch per causal query.

Contracts under test:

  * STEADY-STATE ``ate()`` IS ONE DISPATCH — on BOTH engines the uncached
    query (subpopulation filter + keep mask + canonical reduction) is one
    compiled program launch plus one scalar-sized ``device_get``; a cached
    repeat issues ZERO dispatches and zero transfers (the version-tagged
    host cache — the residual ``np.asarray(keep)`` host sync of the legacy
    estimate path is gone).
  * BIT-IDENTITY ACROSS PIPELINES — the fused query, the planner-era
    ``assemble`` baseline (canonical reassembly first) and the offline
    recompute agree: fused vs assemble bitwise (shared canonical
    estimator, capacity-invariant chunked reduction), vs offline to float
    tolerance.
  * ROUTED ROW LOOKUP — ``matched_rows`` probes hash to their owning
    partition and binary-search only that partition's table (all-to-all
    routed on a mesh); masks are identical to the broadcast-search
    baseline and the offline CEM row mask.
  * CAPACITY SHRINK AFTER EVICTION — when TTL eviction collapses the live
    set below 1/4 of grown capacity, the engine compacts into a smaller
    capacity and ``state_bytes()`` decreases; the stream then continues
    exactly (and at one dispatch per ingest) at the smaller shape.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import pytest

from repro.core import CoarsenSpec, OnlineEngine, PartitionedOnlineEngine
from repro.core import cem as cem_fn
from repro.core.ate import estimate_ate
from repro.core.online import BASE_VIEW
from repro.data.columnar import Table
from repro.launch.trace import count_dispatches

SPECS = {"x0": CoarsenSpec.categorical(5), "x1": CoarsenSpec.categorical(4),
         "x2": CoarsenSpec.categorical(3)}
TREATMENTS = {"ta": ["x0", "x1"], "tb": ["x0", "x2"]}
SUBPOPS = (None, {"x2": [0]}, {"x2": [1, 2]}, {"x0": [0, 1], "x2": [0, 2]})


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


def _engines():
    kw = dict(query_dims=("x2",))
    return {
        "replicated": OnlineEngine(SPECS, TREATMENTS, "y", granule=256,
                                   **kw),
        "partitioned": PartitionedOnlineEngine(SPECS, TREATMENTS, "y",
                                               granule=64, n_parts=3, **kw),
    }


def _feed(engines, n_batches=3, size=500, seed0=10):
    batches = []
    for i in range(n_batches):
        cols, valid = _frame(size, seed=seed0 + i)
        b = Table.from_numpy(cols, valid)
        batches.append((cols, valid))
        for eng in engines.values():
            eng.ingest(b)
    return batches


EST_FIELDS = ("ate", "att", "variance", "n_matched_treated",
              "n_matched_control", "n_groups")


@pytest.mark.parametrize("label", ["replicated", "partitioned"])
def test_steady_state_ate_is_one_dispatch_and_cached_is_zero(label):
    engines = _engines()
    _feed(engines)
    eng = engines[label]
    for t in sorted(TREATMENTS):
        for sub in SUBPOPS:
            eng.ate(t, subpopulation=sub)     # warm the program traces
    # mutate state so every cache entry drops, then query steady-state
    cols, valid = _frame(400, seed=77)
    eng.ingest(Table.from_numpy(cols, valid))
    for t in sorted(TREATMENTS):
        for sub in SUBPOPS:
            # the guard proves the query path's only host<->device moves
            # are the explicit device_put/device_get it owns
            with count_dispatches() as n, jax.transfer_guard("disallow"):
                est = eng.ate(t, subpopulation=sub)
            assert n() == 1, (label, t, sub, n())
            # the estimate was fetched with the query's single device_get:
            # reading it is free (host scalars, no implicit transfer)
            assert isinstance(float(est.ate), float)
            with count_dispatches() as n, jax.transfer_guard("disallow"):
                est2 = eng.ate(t, subpopulation=sub)
            assert n() == 0, (label, t, sub, "cached query dispatched")
            assert float(est2.ate) == float(est.ate)
    # the query label sees exactly the fused query program
    eng._cache.clear()
    with count_dispatches(label="query") as n:
        eng.ate("ta")
    assert n() == 1


def test_fused_query_bit_identical_to_assemble_and_close_to_offline():
    engines = _engines()
    history = _feed(engines, n_batches=4, size=600)
    cols = {k: np.concatenate([c[k] for c, _ in history])
            for k in history[0][0]}
    valid = np.concatenate([v for _, v in history])
    full = Table.from_numpy(cols, valid)
    for t in sorted(TREATMENTS):
        ests = {}
        for label, eng in engines.items():
            ests[f"{label}/fused"] = eng._estimate(t, None, pipeline="fused")
            ests[f"{label}/assemble"] = eng._estimate(t, None,
                                                      pipeline="assemble")
        vals = {k: {f: float(getattr(e, f)) for f in EST_FIELDS}
                for k, e in ests.items()}
        first = next(iter(vals.values()))
        for k, v in vals.items():
            assert v == first, (t, k, v, first)
        # and the maintained state agrees with the offline recompute
        dims = sorted(set(TREATMENTS[t]) | {"x2"})
        want = estimate_ate(cem_fn(
            full, t, "y", {d: SPECS[d] for d in dims}).groups)
        np.testing.assert_allclose(first["ate"], float(want.ate),
                                   rtol=1e-5, atol=1e-6)
        assert first["n_groups"] == int(want.n_groups)


def test_matched_rows_routed_equals_assemble_and_offline():
    engines = _engines()
    history = _feed(engines, n_batches=3, size=700, seed0=40)
    cols = {k: np.concatenate([c[k] for c, _ in history])
            for k in history[0][0]}
    valid = np.concatenate([v for _, v in history])
    probe = Table.from_numpy(cols, valid)
    for t in sorted(TREATMENTS):
        dims = sorted(set(TREATMENTS[t]) | {"x2"})
        offline = cem_fn(probe, t, "y", {d: SPECS[d] for d in dims})
        want = np.asarray(offline.table.valid)
        for label, eng in engines.items():
            fused = np.asarray(eng.matched_rows(t, probe))
            assemble = np.asarray(
                eng.matched_rows(t, probe, pipeline="assemble"))
            np.testing.assert_array_equal(fused, assemble,
                                          err_msg=f"{label}/{t}")
            np.testing.assert_array_equal(fused, want,
                                          err_msg=f"{label}/{t} offline")
    # steady state: the fused row lookup is one compiled dispatch
    for label, eng in engines.items():
        eng.matched_rows("ta", probe)                   # warm trace
        with count_dispatches() as n:
            eng.matched_rows("ta", probe)
        assert n() == 1, (label, n())


def test_cem_groups_served_from_version_memoized_assembly():
    engines = _engines()
    _feed(engines)
    rep, part = engines["replicated"], engines["partitioned"]
    for t in sorted(TREATMENTS):
        a = rep.cem_groups(t)
        b = part.cem_groups(t)
        ka = np.asarray(a.keep)[np.asarray(a.keep)].shape
        kb = np.asarray(b.keep)[np.asarray(b.keep)].shape
        assert ka == kb
        assert float(estimate_ate(a).ate) == float(estimate_ate(b).ate)
    # repeated partitioned queries reuse the memoized assembly: no new
    # dispatches until the next committed state mutation
    part.cem_groups("ta")
    with count_dispatches() as n:
        part.cem_groups("ta")
        part.cem_groups("ta")
    assert n() == 0
    cols, valid = _frame(300, seed=5)
    part.ingest(Table.from_numpy(cols, valid))
    with count_dispatches() as n:
        part.cem_groups("ta")
    assert n() >= 1          # version bumped -> assembly recomputed


@pytest.mark.parametrize("label", ["replicated", "partitioned"])
def test_capacity_shrink_after_eviction_reclaims_memory(label):
    # wide key space (240 combos) at granule 64 -> capacity grows; then
    # the live set collapses to a handful of groups and eviction + the
    # shrink pass must hand the memory back
    specs = {"x0": CoarsenSpec.categorical(8),
             "x1": CoarsenSpec.categorical(6),
             "x2": CoarsenSpec.categorical(5)}
    treatments = {"t": ["x0", "x1", "x2"]}

    def frame(n, seed, hi=(8, 6, 5)):
        r = np.random.default_rng(seed)
        c = {"x0": r.integers(0, hi[0], n).astype(np.int32),
             "x1": r.integers(0, hi[1], n).astype(np.int32),
             "x2": r.integers(0, hi[2], n).astype(np.int32)}
        c["t"] = (r.random(n) < 0.5).astype(np.int32)
        c["y"] = np.round(r.normal(0, 1, n)).astype(np.float32)
        return c

    if label == "replicated":
        eng = OnlineEngine(specs, treatments, "y", granule=64,
                           delta_granule=1024)
    else:
        eng = PartitionedOnlineEngine(specs, treatments, "y", granule=64,
                                      delta_granule=1024, n_parts=2)
    for i in range(4):
        eng.ingest(Table.from_numpy(frame(600, seed=i)))
    cap_before = eng._view_table(BASE_VIEW).capacity
    bytes_before = eng.state_bytes()["total"]
    assert cap_before > eng._shrink_granule()   # the stream really grew
    # last batch touches only 2 combos; ttl=0 evicts everything else
    eng.ingest(Table.from_numpy(frame(200, seed=99, hi=(1, 2, 1))))
    evicted = eng.evict(ttl=0)
    assert evicted[BASE_VIEW] > 0
    assert eng._view_table(BASE_VIEW).capacity < cap_before
    assert eng.state_bytes()["total"] < bytes_before
    # surviving stats are exact: the 2 live groups carry their FULL
    # accumulated sums (eviction compaction is a gather, shrink a slice)
    live = {}
    for i in list(range(4)) + [99]:
        c = frame(600 if i < 4 else 200, seed=i,
                  hi=(8, 6, 5) if i < 4 else (1, 2, 1))
        for j in range(len(c["t"])):
            key = (c["x0"][j], c["x1"][j], c["x2"][j])
            acc = live.setdefault(key, [0.0, 0.0])
            acc[0] += 1.0
            acc[1] += float(c["y"][j])
    survivors = {(0, 0, 0), (0, 1, 0)}
    tab = eng._view_table(BASE_VIEW)
    gv = np.asarray(tab.group_valid).reshape(-1)
    one = np.asarray(tab.stats["one"]).reshape(-1)[gv]
    ysum = np.asarray(tab.stats["y"]).reshape(-1)[gv]
    assert gv.sum() == len(survivors)
    want = sorted((live[k][0], live[k][1]) for k in survivors)
    got = sorted(zip(one.tolist(), ysum.tolist()))
    assert got == want
    # the stream continues exactly at the smaller shape, one dispatch
    eng.ingest(Table.from_numpy(frame(600, seed=5)))
    eng.ingest(Table.from_numpy(frame(600, seed=6)))
    with count_dispatches() as n:
        eng.ingest(Table.from_numpy(frame(600, seed=7)))
    assert n() == 1
    # queries still answer (and for the partitioned engine the fused and
    # assemble paths still agree bitwise post-shrink)
    f = eng._estimate("t", None, pipeline="fused")
    a = eng._estimate("t", None, pipeline="assemble")
    assert float(f.ate) == float(a.ate)
    assert float(f.variance) == float(a.variance)


# ----------------------------- mesh (subprocess, forced host devices) -------
def _run_subprocess(body: str):
    code = textwrap.dedent(body)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=900,
                          env={**os.environ, "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


def test_mesh_query_single_dispatch_and_routed_lookup_bit_identical():
    out = _run_subprocess("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    assert jax.device_count() == 4
    from repro.core import CoarsenSpec, OnlineEngine, PartitionedOnlineEngine
    from repro.data.columnar import Table
    from repro.launch.mesh import make_data_mesh
    from repro.launch.trace import count_dispatches

    SPECS = {"x0": CoarsenSpec.categorical(5),
             "x1": CoarsenSpec.categorical(4),
             "x2": CoarsenSpec.categorical(3)}
    TREATMENTS = {"ta": ["x0", "x1"], "tb": ["x0", "x2"]}

    def frame(n, seed):
        rng = np.random.default_rng(seed)
        cols = {"x0": rng.integers(0, 5, n).astype(np.int32),
                "x1": rng.integers(0, 4, n).astype(np.int32),
                "x2": rng.integers(0, 3, n).astype(np.int32)}
        cols["ta"] = (rng.random(n) < 0.2 + 0.5 * cols["x0"] / 4
                      ).astype(np.int32)
        cols["tb"] = (rng.random(n) < 0.4).astype(np.int32)
        cols["y"] = np.round(2.0 * cols["ta"] + 1.5 * cols["x0"]
                             + rng.normal(0, 0.5, n)).astype(np.float32)
        return cols, rng.random(n) > 0.08

    mesh = make_data_mesh(4)
    ref = OnlineEngine(SPECS, TREATMENTS, "y", granule=256,
                       query_dims=("x2",))
    eng = PartitionedOnlineEngine(SPECS, TREATMENTS, "y", granule=256,
                                  mesh=mesh, n_parts=8, query_dims=("x2",))
    feeds = []
    for i in range(3):
        cols, valid = frame(1000, seed=i)
        feeds.append((cols, valid))
        b = Table.from_numpy(cols, valid)
        ref.ingest(b)
        eng.ingest(b)
    probe = Table.from_numpy(
        {k: np.concatenate([c[k] for c, _ in feeds]) for k in feeds[0][0]},
        np.concatenate([v for _, v in feeds]))
    subpops = (None, {"x2": [0]}, {"x0": [0, 1], "x2": [1, 2]})
    for t in sorted(TREATMENTS):
        for sub in subpops:
            eng.ate(t, subpopulation=sub)      # warm
        eng.matched_rows(t, probe)             # warm
    cols, valid = frame(1000, seed=9)
    b = Table.from_numpy(cols, valid)
    ref.ingest(b)
    eng.ingest(b)
    probe2 = Table.from_numpy(cols, valid)
    for t in sorted(TREATMENTS):
        for sub in subpops:
            with count_dispatches() as n:
                got = eng.ate(t, subpopulation=sub)
            assert n() == 1, (t, sub, n())
            want = ref.ate(t, subpopulation=sub)
            for f in ("ate", "att", "variance", "n_matched_treated",
                      "n_groups"):
                assert float(getattr(got, f)) == float(getattr(want, f)), \
                    (t, sub, f)
        # routed row lookup on the mesh == single-device broadcast search
        with count_dispatches() as n:
            routed = np.asarray(eng.matched_rows(t, probe2))
        assert n() == 1, (t, n())
        np.testing.assert_array_equal(routed,
                                      np.asarray(ref.matched_rows(t, probe2)))
        np.testing.assert_array_equal(
            np.asarray(eng.matched_rows(t, probe)),
            np.asarray(ref.matched_rows(t, probe)))
    # eviction (with the shrink pass wired in) stays bit-identical on
    # sharded (P, C) state; this schema's key space (60 combos) cannot
    # outgrow the per-partition granule floor, so no shrink triggers here
    # (the strict state_bytes-decrease regression runs in-process in
    # test_capacity_shrink_after_eviction_reclaims_memory)
    narrow = {k: v[:200].copy() for k, v in cols.items()}
    for k in ("x0", "x1", "x2"):
        narrow[k][:] = 0
    nb = Table.from_numpy(narrow, np.ones(200, bool))
    ref.ingest(nb)
    eng.ingest(nb)
    before = eng.state_bytes()
    ref.evict(ttl=0)
    eng.evict(ttl=0)
    after = eng.state_bytes()
    assert after["total"] <= before["total"], (before, after)
    for t in sorted(TREATMENTS):
        assert float(eng.ate(t).ate) == float(ref.ate(t).ate), t
    print("MESH_QUERY_OK")
    """)
    assert "MESH_QUERY_OK" in out


def test_chunked_sum_is_padding_invariant():
    from repro.kernels.segment_stats import canonical_sum
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, 700).astype(np.float32)
    a = float(canonical_sum(jnp.asarray(x)))
    for pad in (0, 324, 1024, 3000):
        b = float(canonical_sum(jnp.asarray(
            np.concatenate([x, np.zeros(pad, np.float32)]))))
        assert a == b, pad
    # and it agrees with plain sums to float tolerance
    np.testing.assert_allclose(a, float(np.sum(x.astype(np.float64))),
                               rtol=1e-5)
