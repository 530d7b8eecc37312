"""The per-layer metrics read from the program's own records, on a CPU
rehearsal of the ingest cell at a tiny size under a profiler trace.

Spans (``wal.*``, ``engine.*``, ``durable.*``) and the compile counter
are read as on the chip. A CPU trace has no TPU plane, so the readers of
the fused ingest program's stages find no device ops and read nothing;
they are then fed the program's own instruction names, which is what a
TPU trace's device ops are called.
"""
import json
import pathlib

from chipbench import harness
from repro.launch import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 2 ** 31 + 4242
STAGE_METRICS = ("ingest.build_ms_per_batch", "ingest.probe_ms_per_batch",
                 "ingest.resort_ms_per_batch",
                 "ingest.relocate_ms_per_batch",
                 "ingest.touch_remap_ms_per_batch")
PROGRAM_METRICS = ("wal.append_ms_per_batch", "wal.fsync_ms_per_batch",
                   "engine.host_ms_per_batch", "setup.compile_s")


def reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py",
                               "chipbench_metric_" + name.replace(".", "_"))


def test_program_metrics_on_a_cpu_trace():
    cell = harness.find_cell("flightdelay_us.ingest")
    mine = [m for m in cell.per_layer
            if m["name"] in STAGE_METRICS + PROGRAM_METRICS]
    assert len(mine) == 9
    tiny = harness.Cell(cell.name, 1,
                        harness.load_json(DATA / "tiny_config.json"),
                        harness.load_json(DATA / "tiny_ingest.json"),
                        cell.end_to_end, mine)
    trace.clear_spans()
    result = harness.run_cell(tiny, SEED, 1.0, True, log=lambda line: None)
    json.dumps(result)
    assert result["correct"], result["compared"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(got) == set(PROGRAM_METRICS)       # no TPU plane here
    assert all(v > 0 for v in got.values()), got
    recs = trace.spans()
    acked = sum(sp.name == "durable.commit" for sp in recs)
    assert acked == result["attempted"] > 0
    assert not any(sp.name == "compile" for sp in recs)

    # the device ops of a TPU trace are named by the program's compiled
    # instructions: one second each of every op in a stage, and of as
    # many in none as the readers allow
    ops = trace.op_stages("ingest")
    assert ops
    staged = [n for n, op in ops.items() if op.leaf and op.stage]
    unmapped = [n for n, op in ops.items() if op.leaf and not op.stage]
    allowed = int(trace.UNMAPPED_SHARE * len(staged))
    assert 0 < allowed < len(unmapped)

    def observe(names):
        return harness.Observation(
            trace=type("Reduced", (), {"top_ops": [(n, 1.0)
                                                   for n in names]})(),
            counters={"batches_acked": acked})

    obs = observe(staged + unmapped[:allowed])
    for name in STAGE_METRICS:
        value = reader(name).read(obs)
        assert value is not None and value > 0, name
    total = sum(reader(n).read(obs) for n in STAGE_METRICS)
    assert total <= 1e3 * len(staged) / acked
    # past the share, no stage can be told from the others
    obs = observe(staged + unmapped[:allowed + len(staged) // 10])
    assert all(reader(n).read(obs) is None for n in STAGE_METRICS)
