"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload flightdelay_us.ingest --seed 7 \\
        --seconds 30 --trace 0

Refuses (exit 2, no result) where JAX finds no TPU or fewer chips than the
cell asks for. Sets up from the seed, measures for ``--seconds``, checks
what the window produced against the float64 reference, and prints on
standard error every number compared beside its limit; the last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace
1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``compared``.
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
sys.path.insert(1, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

from chipbench import harness, peaks  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    try:
        device = harness.require_device(cell.chips)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    from chipbench import system
    cache = system.enable_compile_cache()
    print(f"device: {device}, peaks {peaks.for_kind(device['kind'])}; "
          f"compile cache {cache}", file=sys.stderr)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), device)
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
