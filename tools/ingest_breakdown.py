"""Where one traced window of an ingest cell spends its time, by stage
of the fused ingest program and by span of the host path.

    python tools/ingest_breakdown.py --seed 7 [--seconds 30] \\
        [--workload flightdelay_us.ingest]

Sets the cell up on the chip and records its window as
``chipbench/run.py --trace 1`` does, from the benchmark's own pieces
(its driver, ``chipbench.tracing``'s recording and reduction). Prints
one JSON object of:

* ``device_ms``: device ms per acknowledged batch of each stage of
  ``repro.launch.trace.INGEST_STAGES``, of leaf ops of the ingest
  program in no stage (``null``), of ops of no ingest program
  (``other``), and of control flow (``nested``, not part of busy time);
* ``busy_ms`` and ``idle_ms``: the window's device busy and idle ms per
  batch;
* ``host_ms``: per span name, the self time per batch of the program's
  spans, and the total per batch of the benchmark's own spans;
* ``counters``: the program's recorder counters per batch
  (``wal.records``, ``wal.bytes``, ``wal.fsyncs``) and in all
  (``ingest.redispatches``: 0 unless a batch grew a capacity, which
  runs a second program inside the window);
* ``compile_spans``: compile spans recorded inside the window;
* ``op_stages_s``: the seconds ``op_stages("ingest")`` took, as the
  stage readers first call it;
* ``rows_per_s`` and ``correct``: the window's rate and its check
  against the reference.
"""
import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from chipbench import harness, system, tracing  # noqa: E402
from repro.launch import trace  # noqa: E402

PER_BATCH = ("wal.records", "wal.bytes", "wal.fsyncs")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="flightdelay_us.ingest")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    harness.require_device(cell.chips)
    system.enable_compile_cache()
    driver_mod = harness.load_module(
        cell.home / "drivers" / f"{cell.traffic['driver']}.py",
        f"chipbench_driver_{cell.traffic['driver']}")
    with tempfile.TemporaryDirectory(prefix="ingest_breakdown_") as work:
        ctx = harness.Context(cell.config, cell.traffic, args.seed, work,
                              harness.log_stderr, tracing.Spans(True))
        drv = driver_mod.Driver(ctx)
        try:
            drv.setup()
            log_dir = os.path.join(work, "trace")
            with tracing.recording(log_dir):
                with ctx.span(tracing.WINDOW):
                    drv.window(args.seconds)
            raw = tracing.Trace.load(tracing.trace_file(log_dir))
            red = tracing.reduce(raw)
            rate = drv.end_to_end().get("ingest_rows_per_s")
            n = drv.counters()["batches_acked"]
            correct = drv.check().correct
        finally:
            drv.close()

    t = time.perf_counter()
    ops = trace.op_stages("ingest")
    op_stages_s = time.perf_counter() - t
    device_ms = {}
    for name, secs in red.top_ops:
        op = ops.get(name)
        key = ("other" if op is None else
               "nested" if not op.leaf else str(op.stage))
        device_ms[key] = device_ms.get(key, 0.0) + 1e3 * secs / n
    recs = trace.spans()
    own = trace.self_times(recs)
    host_ms = {}
    for sp in recs:
        host_ms[sp.name] = host_ms.get(sp.name, 0.0) + own[sp.id] / 1e6 / n
    for ev in raw.spans:
        if ev.name != tracing.WINDOW:
            key = "bench." + ev.name
            ms = (ev.end - ev.start) / 1e6 / n
            host_ms[key] = host_ms.get(key, 0.0) + ms
    counts = trace.counters()
    print(json.dumps({
        "batches": n,
        "device_ms": device_ms,
        "busy_ms": red.busy_ns / 1e6 / n,
        "idle_ms": (red.window_ns - red.busy_ns) / 1e6 / n,
        "host_ms": host_ms,
        "counters": {**{k: counts.get(k, 0) / n for k in PER_BATCH},
                     "ingest.redispatches":
                     counts.get("ingest.redispatches", 0)},
        "compile_spans": sum(sp.name == "compile" for sp in recs),
        "op_stages_s": op_stages_s,
        "rows_per_s": rate,
        "correct": correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
