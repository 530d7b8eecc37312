"""CPU rehearsal of every driver through the harness, at a tiny size.

Each run skips only the harness's look for a chip: set-up, the measured
window, the result line and the comparison with the float64 reference
run as on the chip. With the timed path broken underneath (a batch that
leaves the state unchanged, half a batch left out, an answer or a row
altered where it is produced, every group filed under another key) the
same runs must come out not correct, and so must the control, the
reference computed in bfloat16.
"""
import json
import pathlib

import numpy as np
import pytest

from chipbench import control, harness

DATA = pathlib.Path(__file__).resolve().parent / "data"
SECONDS = 1.0
SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold


def tiny_cell() -> harness.Cell:
    cell = harness.find_cell("flightdelay_us.ingest")
    return harness.Cell(cell.name, 1,
                        harness.load_json(DATA / "tiny_config.json"),
                        harness.load_json(DATA / "tiny_ingest.json"),
                        cell.end_to_end, cell.per_layer)


def run(trace: bool = False, logs=None) -> dict:
    log = (logs.append if logs is not None else lambda line: None)
    result = harness.run_cell(tiny_cell(), SEED, SECONDS, trace, log=log)
    json.dumps(result)              # the result line is plain JSON
    return result


def test_driver_rehearsal_is_correct():
    logs = []
    result = run(logs=logs)
    assert result["correct"], result["compared"]
    assert list(result)[-1] == "compared"
    assert result["failed"] == 0 and result["attempted"] > 0
    cell = tiny_cell()
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert any(line.startswith("setup:") for line in logs)
    assert not any("capacity grew" in line for line in logs)


def test_stream_of_new_rows_then_corrections():
    """The window sends the stream's new rows in hour order, and once
    they are used up goes on with corrections of the streamed chunks:
    each retracted as last sent, then sent again revised (or with its
    revision taken back)."""
    from chipbench.drivers import ingest
    drv = ingest.Driver(harness.Context({}, {}, SEED, "", print, None))
    sent = []

    def send(sl, version, sign):
        sent.append((sl.start, version, sign))
        drv.present[sl] = sign > 0
        drv.version[sl] = version
    drv._send = send
    # as set-up leaves it: four 512-row chunks streamed from row 4096,
    # the first sent and corrected once (its revision applied)
    drv.rows, drv.start, drv.end = 512, 4096, 6144
    drv.next, drv.cursor = 4608, 2
    drv.present = np.arange(6144) < 4608
    drv.version = ((np.arange(6144) >= 4096)
                   & (np.arange(6144) < 4608)).astype(np.int8)
    for _ in range(11):
        drv._next_op()
    assert sent == [(4608, 0, 1), (5120, 0, 1), (5632, 0, 1),
                    (4608, 0, -1), (4608, 1, 1), (5120, 0, -1),
                    (5120, 1, 1), (5632, 0, -1), (5632, 1, 1),
                    (4096, 1, -1), (4096, 0, 1)]
    assert drv.window_corrections == 8


def test_control_is_not_correct():
    """The reference in bfloat16, put in the program's place, fails at
    least one of the cell's numbers."""
    from chipbench.drivers import ingest
    r = control.readings(tiny_cell(), SEED, SECONDS, log=lambda line: None)
    assert all(r["program"][k] <= ingest.LIMITS[k] for k in ingest.LIMITS)
    assert any(r["control"][k] > ingest.LIMITS[k] for k in r["control"])


def _break_ingest(monkeypatch, how: str) -> None:
    """Break the engine's ingest: for stream-sized (512-row) batches, or
    with ``relabelled`` for every batch (carriers 0 and 1 swap codes, so
    every group keeps its rows but is filed under another key)."""
    from repro.core.online import OnlineEngine
    from repro.data.columnar import Table
    real = OnlineEngine.ingest

    def broken(self, batch, retract=False):
        if how == "relabelled":
            cols = dict(batch.columns)
            c = np.asarray(cols["carrier"])
            cols["carrier"] = np.where(c < 2, 1 - c, c).astype(c.dtype)
            return real(self, Table.from_numpy(cols, batch.valid), retract)
        if batch.nrows != 512:
            return real(self, batch, retract)
        if how == "unchanged":
            return None
        if how == "half":
            valid = np.asarray(batch.valid).copy()
            valid[256:] = False
            return real(self, Table(batch.columns, valid), retract)
        cols = dict(batch.columns)           # "altered": one row's delay
        if not retract:
            y = np.asarray(cols["dep_delay"]).copy()
            y[0] += 1.0
            cols["dep_delay"] = y
        return real(self, Table.from_numpy(cols, batch.valid), retract)
    monkeypatch.setattr(OnlineEngine, "ingest", broken)


@pytest.mark.parametrize("how", ["unchanged", "half", "altered",
                                 "relabelled"])
def test_broken_ingest_is_not_correct(monkeypatch, how):
    _break_ingest(monkeypatch, how)
    result = run()
    assert not result["correct"], result["compared"]
    if how == "relabelled":      # the same groups, under other keys
        assert result["compared"]["views_differing"][
            "value"] == 4, result["compared"]
