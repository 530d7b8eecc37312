"""Seeds, the refusal of a backend that is not a TPU, and the peak table."""
import pathlib

import numpy as np
import pytest

from chipbench import gen, harness, peaks, run

DATA = pathlib.Path(__file__).resolve().parent / "data"
BIG = 2 ** 33 + 7


def tiny():
    return harness.load_json(DATA / "tiny_config.json")


def test_relation_is_a_function_of_the_seed():
    a, b = gen.generate(tiny(), BIG), gen.generate(tiny(), BIG)
    assert a.keys() == b.keys()
    for k in a:
        assert a[k].tobytes() == b[k].tobytes(), k
    c = gen.generate(tiny(), BIG + 1)
    assert a["dep_delay"].tobytes() != c["dep_delay"].tobytes()


def test_relation_is_in_hour_order_with_whole_minute_delays():
    cfg = tiny()
    rel = gen.generate(cfg, 3)
    assert len(rel["airport"]) == cfg["n_flights"]
    y = rel["dep_delay"]
    assert (y >= 0).all() and (y == np.round(y)).all()
    season = rel["w_season"]
    first = np.argmax(season > 0.5)        # season rises through spring
    assert (season[:first] <= 0.5).all()
    assert rel["airport"].max() < cfg["n_airports"]


def test_zipf_popularity_gives_every_seed_the_same_hubs():
    law = {"law": "zipf", "exponent": 2.5, "offset": 25}
    w = gen.popularity(law, 360, np.random.default_rng(0))
    share = np.sort(w)[::-1] / w.sum()
    assert 0.68 < share[:30].sum() < 0.72
    assert np.array_equal(w, gen.popularity(law, 360,
                                            np.random.default_rng(1)))


def test_cpu_backend_is_refused():
    with pytest.raises(harness.NoChip):
        harness.require_device(1)


def test_run_exits_nonzero_without_a_chip(capsys):
    rc = run.main(["--workload", "flightdelay_us.ingest", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.for_kind("TPU v5 lite")
    assert v5e["bf16_flop_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")
