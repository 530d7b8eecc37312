"""FLIGHTDELAY, online: streaming causal inference over arriving batches.

The offline driver (flight_delay_analysis.py) answers causal queries by
re-running CEM over the full relation. This demo plays the paper's ONLINE
setting instead: flights arrive in batches (think: live feed from the DOT),
and an :class:`repro.core.OnlineEngine` maintains the causal estimates by
delta cuboid maintenance — per batch it touches O(batch + stat table), never
the full history.

Per batch it prints the evolving ATE per weather treatment (vs the planted
ground truth) and the ingest latency; at the end it refreshes a propensity
model from the engine's bounded streaming reservoir (no row log), then
re-runs the offline pipeline over everything ingested to show the
estimates agree and what each refresh would have cost offline.

With ``--devices D`` the stream is row-sharded over a D-device data mesh:
each device aggregates its shard of every batch and the tiny per-device
delta stat tables are all-gathered and combined (off-TPU this forces D
host-platform devices, so it demonstrates the mechanism, not a speedup).
Add ``--partitioned`` to key-range partition the MATERIALIZED views
themselves over the mesh (deltas routed to owner devices, per-device
resident state ~1/D — printed at the end).

With ``--serve`` the demo holds back the final batch and plays the
multi-tenant serving regime: a window of concurrent HETEROGENEOUS
subpopulation queries (different treatments, airports and estimands) is
answered through :class:`repro.core.serving.ServingEngine` — duplicates
collapse in flight, cache hits skip the device entirely, and the fresh
specs of a wave cost ONE batched compiled dispatch. The held-back batch
is then ingested live to show invalidation: repeating the same queries
re-dispatches against the new state instead of serving stale estimates.

Run:  PYTHONPATH=src python examples/online_flight_delay.py \
          [--flights N] [--batches K] [--devices D] [--partitioned] \
          [--serve]
"""
import argparse
import os
import time

_pre = argparse.ArgumentParser(add_help=False)
_pre.add_argument("--devices", type=int, default=1)
_n_dev = _pre.parse_known_args()[0].devices
if _n_dev > 1:  # must precede any jax import; preserve existing flags
    os.environ["XLA_FLAGS"] = (
        f"{os.environ.get('XLA_FLAGS', '')} "
        f"--xla_force_host_platform_device_count={_n_dev}").strip()

import numpy as np

from repro.core import (CoarsenSpec, OnlineEngine, PartitionedOnlineEngine,
                        cem, estimate_ate)
from repro.data import flightgen
from repro.data.columnar import Table
from repro.data.join import fk_join
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_data_mesh

SPEC_RANGES = {"w_precipm": (0, 3), "w_wspdm": (0, 80), "w_tempm": (-20, 40)}
COVARIATES = {
    "thunder": ["w_precipm", "w_wspdm"],
    "snow": ["w_tempm", "w_wspdm"],
    "highwind": ["w_precipm", "w_tempm"],
}


def build_specs():
    specs = {
        "airport": CoarsenSpec.categorical(16),
        "carrier": CoarsenSpec.categorical(16),
        "traffic": CoarsenSpec.equal_width(0, 40, 8),
        "w_season": CoarsenSpec.equal_width(0, 1, 4),
    }
    for name, (lo, hi) in SPEC_RANGES.items():
        specs[name] = CoarsenSpec.equal_width(lo, hi, 5)
    return specs


def serve_demo(engine, cols, valid, held_back):
    """Multi-tenant serving against live ingest: one wave of mixed
    subpopulation queries = one batched dispatch; a live ingest then
    invalidates the estimate cache so repeats re-dispatch."""
    from repro.core.serving import QuerySpec, ServingEngine
    from repro.launch.trace import count_dispatches

    print("\n== serving: concurrent heterogeneous queries "
          "(slot-batched, one dispatch per wave) ==")
    tnames = list(COVARIATES)
    specs = [QuerySpec.make(tnames[i % len(tnames)],
                            subpopulation={"airport": [i % 4]},
                            estimand=("ate", "att")[i % 2])
             for i in range(12)]
    specs += specs[:3]              # concurrent duplicates: collapse in flight
    srv = ServingEngine(engine, n_slots=8)
    with count_dispatches(label="query") as n:
        t0 = time.perf_counter()
        served = srv.serve(specs)
        dt = time.perf_counter() - t0
    print(f"   {len(specs)} queries ({len(set(specs))} distinct) -> "
          f"{n()} compiled dispatches in {srv.n_waves} waves, "
          f"{srv.n_deduped} deduped in flight, {dt * 1e3:.1f}ms total")
    for q in served[:4]:
        s = q.spec
        print(f"   {s.estimand.upper()}({s.treatment} | "
              f"airport={s.subpopulation[0][1][0]}) = {q.value:7.2f}")

    s, e = held_back
    print(f"   -- live ingest of {e - s:,} held-back rows "
          "(bumps state version, invalidates served estimates) --")
    engine.ingest(Table.from_numpy({k: v[s:e] for k, v in cols.items()},
                                   valid[s:e]))
    with count_dispatches(label="query") as n:
        again = srv.serve(specs[:6])
    stale = sum(a.value == b.value
                for a, b in zip(again, served[:6]))
    print(f"   same 6 queries after ingest: {n()} fresh dispatch(es), "
          f"{stale}/6 unchanged estimates (cache served {srv.n_cache_served}"
          " hits total)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--flights", type=int, default=200_000)
    ap.add_argument("--airports", type=int, default=8)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--devices", type=int, default=1,
                    help="shard ingest over a data mesh of this many devices")
    ap.add_argument("--partitioned", action="store_true",
                    help="key-range partition the materialized views over "
                         "the mesh (state ~1/D per device)")
    ap.add_argument("--serve", action="store_true",
                    help="demo the slot-batched query server: concurrent "
                         "heterogeneous subpopulation queries against "
                         "live ingest")
    args = ap.parse_args()

    print(f"== generating {args.flights:,} flights, joining weather ==")
    data = flightgen.generate(n_flights=args.flights,
                              n_airports=args.airports, seed=0)
    joined = fk_join(data.flights, data.weather,
                     on={"airport": 64, "hour": 1 << 17}, prefix="w_")
    cols = joined.to_numpy()
    valid = cols.pop("_valid")
    n = len(valid)

    specs = build_specs()
    shared = ["airport", "carrier", "traffic", "w_season"]
    treatments = {t: shared + c for t, c in COVARIATES.items()}
    mesh = make_data_mesh(args.devices) if args.devices > 1 else None
    if mesh is not None:
        print(f"== sharding ingest over {args.devices}-device data mesh ==")
    if args.partitioned:
        print("== key-range partitioned views: each device owns "
              f"1/{max(args.devices, 1)} of every stat table ==")
        engine = PartitionedOnlineEngine(specs, treatments,
                                         outcome="dep_delay",
                                         query_dims=("airport",), mesh=mesh)
    else:
        engine = OnlineEngine(specs, treatments, outcome="dep_delay",
                              query_dims=("airport",), mesh=mesh)

    # seed with the first half, stream the rest
    seed_n = n // 2
    edges = np.linspace(seed_n, n, args.batches + 1).astype(int)
    slices = [(0, seed_n)] + list(zip(edges[:-1], edges[1:]))

    print(f"\n== streaming {len(slices)} batches "
          f"(seed {seed_n:,} rows, then ~{(n - seed_n) // args.batches:,} "
          "rows/batch) ==")
    hdr = " ".join(f"{t:>9s}" for t in COVARIATES)
    print(f"{'batch':>6s} {'rows':>9s} {'ingest':>8s} {hdr}   (truth: "
          + ", ".join(f"{t}={data.true_sate[t]:.1f}" for t in COVARIATES)
          + ")")
    held_back = None
    if args.serve:                  # keep one live batch for the serve demo
        held_back = slices.pop()
    for i, (s, e) in enumerate(slices):
        batch = Table.from_numpy({k: v[s:e] for k, v in cols.items()},
                                 valid[s:e])
        t0 = time.perf_counter()
        rep = engine.ingest(batch)
        dt = time.perf_counter() - t0
        ates = " ".join(f"{float(engine.ate(t).ate):9.2f}"
                        for t in COVARIATES)
        tag = "" if all(rep.fast_path.values()) else "  [grew]"
        print(f"{i:6d} {e - s:9,d} {dt:7.2f}s {ates}{tag}")

    print("\n== online sub-population queries (materialized, cached) ==")
    for airport in (0, 1):
        t0 = time.perf_counter()
        est = engine.ate("thunder", subpopulation={"airport": [airport]})
        dt = time.perf_counter() - t0
        print(f"   ATE(thunder | airport={airport}) = {float(est.ate):7.2f}"
              f"   [{dt * 1e3:.1f}ms]")
    t0 = time.perf_counter()
    engine.ate("thunder", subpopulation={"airport": [0]})
    print(f"   repeat query: {(time.perf_counter() - t0) * 1e6:.0f}us "
          f"(cache hits={engine.cache_hits})")

    if args.serve:
        serve_demo(engine, cols, valid, held_back)

    print("\n== streaming propensity (bounded reservoir, no row log) ==")
    t0 = time.perf_counter()
    model = engine.refresh_propensity("thunder",
                                      ["traffic", "w_precipm", "w_wspdm"])
    dt = time.perf_counter() - t0
    print(f"   fit over {int(engine.stream.n):,} streamed rows via "
          f"{engine.stream.capacity:,}-row reservoir in {dt:.2f}s "
          f"(converged={bool(model.converged)})")

    print("\n== offline recompute over everything ingested (the "
          "per-refresh cost this engine avoids) ==")
    full = Table.from_numpy(cols, valid)
    for t in COVARIATES:
        tspecs = {c: specs[c] for c in treatments[t]}
        t0 = time.perf_counter()
        offline = estimate_ate(cem(full, t, "dep_delay", tspecs).groups)
        dt = time.perf_counter() - t0
        online = engine.ate(t)
        print(f"   {t:9s} offline {float(offline.ate):7.2f} in {dt:5.2f}s"
              f" | online {float(online.ate):7.2f} from materialized state"
              f" | truth {data.true_sate[t]:6.2f}")

    sb = engine.state_bytes()
    print(f"\n== materialized state: {sb['total']:,} B total, "
          f"{sb['per_device']:,} B per device ==")


if __name__ == "__main__":
    enable_compile_cache()
    main()
