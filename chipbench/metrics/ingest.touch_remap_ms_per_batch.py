"""Device time per acknowledged batch in the fused ingest program's
touch remap (every old key looked up in the re-sorted table): the
trace's leaf-op seconds that the program's
``repro.launch.trace.stage_ms_per_batch`` maps to the stages below. None
where the program names no stages, or where its stages cannot be told
apart in the trace (a CPU trace has no device ops of the program)."""

STAGES = ("touch_remap",)


def read(obs):
    try:
        from repro.launch.trace import stage_ms_per_batch
    except ImportError:
        return None
    return stage_ms_per_batch(obs.trace.top_ops,
                              obs.counters.get("batches_acked"), STAGES)
