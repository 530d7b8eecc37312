"""The reduction from a profiler trace to the benchmark's numbers, on a
small trace recorded on a TPU v5e (``record_trace.py``) and on a
synthetic one whose answers are known."""
import pathlib

import pytest

from chipbench import harness, tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"
SMALL = DATA / "small.xplane.pb"


@pytest.fixture(scope="module")
def small():
    return tracing.Trace.load(str(SMALL))


def _raw(name_filter):
    """Events of the device's line ``name_filter`` straight from
    ``ProfileData``, independently of :class:`tracing.Trace`."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(SMALL))
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == name_filter:
                    out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
    return out


def test_small_trace_has_one_device_and_every_span(small):
    assert list(small.ops) == ["/device:TPU:0"]
    names = [sp.name for sp in small.spans]
    assert names.count(tracing.WINDOW) == 1
    for name in ("batch_build", "journal_dispatch", "commit", "wave_step",
                 "generator_sleep"):
        assert names.count(name) == 3, name


def test_small_trace_reduction(small):
    r = tracing.reduce(small)
    win = next(sp for sp in small.spans if sp.name == tracing.WINDOW)
    assert r.window_ns == win.end - win.start
    ops = [(s, e) for _, s, e in _raw("XLA Ops")]
    busy = tracing.union(tracing.clip(ops, win.start, win.end))
    assert r.busy_ns == pytest.approx(sum(e - s for s, e in busy))
    assert 0 < r.busy_ns < r.window_ns
    assert r.busy_ns + sum(g for _, g in r.gaps) * 1e9 == pytest.approx(
        r.window_ns)
    mods = _raw("XLA Modules")
    step = sum(e - s for n, s, e in mods if n.startswith("jit_step"))
    probe = sum(e - s for n, s, e in mods if n.startswith("jit_probe"))
    assert r.module_ns("journal_dispatch") == pytest.approx(step)
    assert r.module_ns("wave_step") == pytest.approx(probe)
    assert any(name.startswith("sort") for name, _ in r.top_ops[:5])
    # the longest idle gaps are the host-only spans
    assert {n for n, _ in r.gaps[:6]} <= {"batch_build", "commit",
                                          "generator_sleep", "other"}
    assert 0 < r.idle_share < 1


def test_per_layer_readers_on_the_small_trace(small):
    r = tracing.reduce(small)
    read = lambda name, counters: harness.load_module(
        harness.HERE / "metrics" / f"{name}.py", "m_" + name.replace(
            ".", "_")).read(harness.Observation(r, counters))
    assert read("ingest.device_ms_per_batch", {"batches_acked": 3}) == \
        pytest.approx(r.busy_ns / 3e6)
    assert read("device.idle_share.ingest", {}) == pytest.approx(
        100 * (1 - r.busy_ns / r.window_ns))
    assert read("ingest.device_ms_per_batch", {"batches_acked": 0}) is None


def test_synthetic_reduction():
    E = tracing.Event
    trace = tracing.Trace(
        ops={"/device:TPU:0": [E("%a = f32[] add(x)", 10, 30),
                               E("%b = f32[] mul(x)", 25, 40),
                               E("%a = f32[] add(x)", 90, 120)]},
        modules={"/device:TPU:0": [E("jit_p(1)", 10, 40),
                                   E("jit_q(2)", 90, 120)]},
        spans=[E("window", 0, 100), E("journal_dispatch", 8, 45),
               E("commit", 45, 110), E("generator_sleep", 95, 100)])
    r = tracing.reduce(trace, slack_ns=0)
    assert r.window_ns == 100
    assert r.busy_ns == 30 + 10            # [10, 40] and [90, 100]
    assert sorted(r.gaps, key=lambda g: -g[1]) == r.gaps
    assert r.gaps[0] == ("commit", 50e-9)
    assert ("other", 10e-9) in r.gaps      # [0, 10] before any span
    assert dict(r.top_ops) == pytest.approx({"a": 30e-9, "b": 15e-9})
    assert r.module_ns("journal_dispatch") == 30
    assert r.module_ns("commit") == 30
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tracing.op_name("%sort.6 = (f32[8]) sort(x)") == "sort.6"
    with pytest.raises(RuntimeError):
        tracing.reduce(tracing.Trace(ops={}, modules={}, spans=[]))
