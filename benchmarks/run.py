"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Mapping to the paper:
  bench_e2e            Fig. 8 (a,b,c): end-to-end CEM, AWMD, ATE vs truth
  bench_quality        Table 3: method-by-method sizes + AWMD (vs oracle)
  bench_scalability    Fig. 9 (a,b): NNM + CEM/EM/subclass scaling
  bench_optimizations  Fig. 9 (c,d): pushdown, factoring, cube, prepared DB
  bench_online         (ours) §4.2 online setting: delta maintenance vs
                       full recompute per streamed batch
  bench_kernels        (ours) Pallas kernels vs jnp references
  bench_roofline       (ours) dry-run roofline table, from results/dryrun.json

Flags / env:
  --json PATH          also write the collected rows + suite statuses as a
                       JSON artifact (CI publishes this as BENCH_*.json)
  --only NAME[,NAME]   run a subset of suites
  REPRO_BENCH_SMOKE=1  reduced problem sizes (CI smoke job)
"""
import argparse
import json
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write results as a JSON artifact")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of suite names")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_e2e, bench_kernels, bench_online,
                            bench_optimizations, bench_quality,
                            bench_roofline, bench_scalability, common)
    print("name,us_per_call,derived")
    suites = [
        ("bench_e2e", bench_e2e.main),
        ("bench_quality", bench_quality.main),
        ("bench_scalability", bench_scalability.main),
        ("bench_optimizations", bench_optimizations.main),
        ("bench_online", bench_online.main),
        ("bench_kernels", bench_kernels.main),
        ("bench_roofline", bench_roofline.main),
    ]
    if args.only:
        only = set(args.only.split(","))
        unknown = only - {n for n, _ in suites}
        if unknown:
            sys.exit(f"unknown suite(s) in --only: {sorted(unknown)}; "
                     f"available: {[n for n, _ in suites]}")
        suites = [(n, f) for n, f in suites if n in only]
    failures = 0
    statuses = {}
    for name, fn in suites:
        t0 = time.perf_counter()
        try:
            fn()
            statuses[name] = "ok"
            print(f"{name}_total,{(time.perf_counter() - t0) * 1e6:.0f},ok",
                  flush=True)
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            traceback.print_exc()
            statuses[name] = f"FAILED:{type(e).__name__}"
            print(f"{name}_total,0,FAILED:{type(e).__name__}", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"smoke": common.smoke(), "suites": statuses,
                       "results": common.RESULTS}, f, indent=2)
        print(f"wrote {args.json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
