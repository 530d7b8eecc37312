"""Run the online causal engine's main path once on the chip and check it.

    python chip_smoke.py              # one chip: FLIGHTDELAY at 2^23 flights
    python chip_smoke.py --chips 4    # partitioned + row-sharded engines on a
                                      # 4-chip mesh against a one-chip engine

The phases live in ``src/repro/launch/smoke.py`` (the tests run them on the
CPU at a tiny size). This script refuses any backend but a TPU, prints what
it measured on the lines before the last, and ends with one JSON line
naming the device. Any failed check exits non-zero without that line.
"""
import argparse
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated FLIGHTDELAY data")
    args = ap.parse_args()

    from repro.launch import smoke
    from repro.launch.compile_cache import enable_compile_cache

    tag = smoke.require_tpu(args.chips)
    cache = pathlib.Path(enable_compile_cache())
    n_cached = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    print(f"device: platform {tag['platform']}, kind {tag['kind']}, "
          f"count {tag['count']}; compile cache {cache} ({n_cached} "
          "entries at start)", flush=True)
    t0 = time.perf_counter()

    def log(line: str) -> None:
        print(f"[{time.perf_counter() - t0:7.1f}s] {line}", flush=True)

    if args.chips == 4:
        smoke.run_mesh(smoke.FULL, args.seed, n_devices=4, log=log)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
            smoke.run_single(smoke.FULL, args.seed, workdir, log=log)
    print(json.dumps({"ok": True, "device": smoke.device_tag()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
