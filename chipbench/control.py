"""Readings that set the limits of ``correct``: per seed, the numbers a
cell compares for the program and for its control, the float64 reference
computed in bfloat16 and put in the program's place.

    python chipbench/control.py --workload flightdelay_us.ingest \\
        --seeds 11 12 13 --seconds 10

Runs each seed's set-up and a window at the cell's own size and load, as
a benchmark run does, then prints one JSON line per seed:
``{"seed": s, "program": {...}, "control": {...}}``. The benchmark's own
runs never run the control.
"""
import argparse
import json
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
sys.path.insert(1, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

from chipbench import harness, tracing  # noqa: E402


def readings(cell: harness.Cell, seed: int, seconds: float,
             log=harness.log_stderr) -> dict:
    """The program's and the control's compared numbers for one seed."""
    mod = harness.load_module(
        cell.home / "drivers" / f"{cell.traffic['driver']}.py",
        f"chipbench_driver_{cell.traffic['driver']}")
    with tempfile.TemporaryDirectory(prefix="chipbench_") as work:
        ctx = harness.Context(cell.config, cell.traffic, seed, work, log,
                              tracing.Spans(enabled=False))
        drv = mod.Driver(ctx)
        try:
            drv.setup()
            drv.window(seconds)
            program = {k: v for k, (v, _) in drv.check().compared.items()}
            control = {k: float(v) for k, v in drv.control().items()}
        finally:
            drv.close()
    return {"seed": seed, "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    try:
        harness.require_device(cell.chips)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    from chipbench import system
    system.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
