"""FLIGHTDELAY end-to-end driver — the paper's §5 experiment, full pipeline.

Pipeline (all stages real, no stubs):
  1. generate flights + weather with planted causal ground truth
     (Table 2's full NRCM: both potential outcomes are materialized, so we
     can SCORE estimates, not eyeball them);
  2. spatio-temporal FK join (paper §4.1);
  3. per-treatment CEM with CDAG-selected covariates -> ATE (Eq. 4) + AWMD
     (Eq. 5) for 5 weather treatments incl. the low-pressure trap;
  4. the §4 optimizations end-to-end: pushdown, covariate factoring
     (Alg. 1), offline preparation (Alg. 2) + online sub-population query.

Run:  PYTHONPATH=src python examples/flight_delay_analysis.py [--flights N]
"""
import argparse
import time

import jax.numpy as jnp

from repro.core import (CoarsenSpec, awmd, cem, cem_join_pushdown,
                        difference_in_means, estimate_ate, prepare,
                        raw_imbalance)
from repro.data import flightgen
from repro.data.columnar import Table
from repro.data.join import fk_join
from repro.launch.compile_cache import enable_compile_cache

SPEC_RANGES = {"w_precipm": (0, 3), "w_wspdm": (0, 80), "w_hum": (0, 100),
               "w_tempm": (-20, 40)}
CO_WEATHER = {
    "thunder": ["w_precipm", "w_wspdm"],
    "lowvis": ["w_precipm", "w_hum"],
    "highwind": ["w_precipm", "w_tempm"],
    "snow": ["w_tempm", "w_wspdm"],
    "lowpressure": ["w_precipm", "w_wspdm", "w_tempm"],
}


def covariate_specs(treatment):
    specs = {
        "airport": CoarsenSpec.categorical(16),
        "carrier": CoarsenSpec.categorical(16),
        "traffic": CoarsenSpec.equal_width(0, 40, 8),
        "w_season": CoarsenSpec.equal_width(0, 1, 4),
    }
    for name in CO_WEATHER[treatment]:
        lo, hi = SPEC_RANGES[name]
        specs[name] = CoarsenSpec.equal_width(lo, hi, 5)
    return specs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--flights", type=int, default=300_000)
    ap.add_argument("--airports", type=int, default=8)
    args = ap.parse_args()

    print(f"== generating {args.flights:,} flights over {args.airports} "
          "airports (1 year) ==")
    t0 = time.perf_counter()
    data = flightgen.generate(n_flights=args.flights,
                              n_airports=args.airports, seed=0)
    print(f"   {time.perf_counter() - t0:.1f}s; weather rows: "
          f"{data.weather.nrows:,}")

    print("\n== spatio-temporal join (paper §4.1) ==")
    t0 = time.perf_counter()
    joined = fk_join(data.flights, data.weather,
                     on={"airport": 64, "hour": 1 << 17}, prefix="w_")
    joined["w_thunder"].block_until_ready()
    print(f"   {time.perf_counter() - t0:.2f}s; rows: {joined.nrows:,}")

    print("\n== per-treatment CEM + ATE (paper Fig. 8) ==")
    print(f"{'treatment':12s} {'naive':>8s} {'CEM ATE':>8s} {'truth':>7s} "
          f"{'|err|':>6s} {'groups':>7s} {'matchedT':>9s} {'time':>6s}")
    for tname in CO_WEATHER:
        mask = flightgen.treatment_valid_mask(data, tname)
        table = Table(dict(joined.columns),
                      joined.valid & jnp.asarray(mask))
        t0 = time.perf_counter()
        res = cem(table, tname, "dep_delay", covariate_specs(tname))
        est = estimate_ate(res.groups)
        ate = float(est.ate)
        dt = time.perf_counter() - t0
        naive = float(difference_in_means(table["dep_delay"], table[tname],
                                          table.valid))
        truth = data.true_sate[tname]
        print(f"{tname:12s} {naive:8.2f} {ate:8.2f} {truth:7.2f} "
              f"{abs(ate - truth):6.2f} {int(est.n_groups):7d} "
              f"{int(est.n_matched_treated):9d} {dt:5.2f}s")

    print("\n== balance (paper Fig. 8(b), AWMD Eq. 5) for thunder ==")
    res = cem(joined, "thunder", "dep_delay", covariate_specs("thunder"))
    covs = {c: joined[c] for c in ("traffic", "w_season", "w_precipm",
                                   "w_wspdm")}
    raw = raw_imbalance(covs, joined["thunder"], joined.valid)
    bal = awmd(res.groups, covs, joined["thunder"], res.table.valid)
    for c in covs:
        print(f"   {c:12s} raw {float(raw[c]):8.4f} -> matched "
              f"{float(bal[c]):8.4f}")

    print("\n== CEM pushdown through the join (paper §4.1, Fig. 9(c)) ==")
    dim_specs = {"season": CoarsenSpec.equal_width(0, 1, 4),
                 "precipm": CoarsenSpec.equal_width(0, 3, 5),
                 "wspdm": CoarsenSpec.equal_width(0, 80, 5)}
    t0 = time.perf_counter()
    pd = cem_join_pushdown(
        data.weather, dim_specs, data.flights,
        {"airport": CoarsenSpec.categorical(16),
         "carrier": CoarsenSpec.categorical(16),
         "traffic": CoarsenSpec.equal_width(0, 40, 8)},
        on={"airport": 64, "hour": 1 << 17}, treatment="thunder",
        outcome="dep_delay", prefix="w_")
    est_pd = estimate_ate(pd.result.groups)
    print(f"   pushdown ATE {float(est_pd.ate):.2f} in "
          f"{time.perf_counter() - t0:.2f}s; weather rows pruned "
          f"{pd.dim_rows_before:,} -> {pd.dim_rows_after:,}")

    print("\n== offline preparation + online queries (Alg. 1 + 2) ==")
    treatments = {t: sorted(covariate_specs(t)) for t in CO_WEATHER}
    all_specs = {}
    for t in CO_WEATHER:
        all_specs.update(covariate_specs(t))
    t0 = time.perf_counter()
    db = prepare(joined, treatments, all_specs, outcome="dep_delay",
                 query_dims=("airport",))
    print(f"   prepared in {time.perf_counter() - t0:.2f}s "
          f"({len(db.cuboids)} cuboids: {list(db.cuboids)})")
    t0 = time.perf_counter()
    for tname in ("thunder", "snow"):
        est = db.ate(tname)
        print(f"   online ATE({tname}) = {float(est.ate):6.2f}   "
              f"[truth {data.true_sate[tname]:.2f}]")
    est_sfo = db.ate("thunder", subpopulation={"airport": [0]})
    print(f"   online ATE(thunder | airport=0) = {float(est_sfo.ate):6.2f}")
    print(f"   3 online queries in {time.perf_counter() - t0:.3f}s "
          "(vs a full CEM pass each without preparation)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
