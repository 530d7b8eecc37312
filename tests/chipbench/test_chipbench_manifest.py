"""``BENCHMARK.json`` against the benchmark's contract, and a new
configuration, traffic mix and per-layer metric added as files alone."""
import hashlib
import json
import pathlib
import re
import shutil

import pytest

from chipbench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    raw = (ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    return json.loads(raw)


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_paths(manifest):
    assert set(manifest) == KEYS
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for word in cmd[1:]:
        assert not word.startswith("/") and ".." not in word
        if (ROOT / word).exists():
            assert any(word.startswith(p + "/") for p in manifest["paths"])
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51


def test_names_units_and_lines(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
        names.append(c["name"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        names.append(w["name"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert len(names) == len(set(names))


def test_cells_configs_and_metrics_fit_together(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = [w["name"] for w in manifest["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in manifest["workloads"]} == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for f in files:
        assert any(f.startswith(p + "/") for p in manifest["paths"])
        assert (ROOT / f).is_file()
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and one_line(m["layer"])
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        reported = [n for n, m in e2e.items()
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])
        c = harness.find_cell(cell)
        assert (c.home / "drivers" / f"{c.traffic['driver']}.py").is_file()
        for m in c.per_layer:
            assert (c.home / "metrics" / f"{m['name']}.py").is_file()


def test_roofline_and_mfu_metrics_are_shares(manifest):
    for m in manifest["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def _digest(root: pathlib.Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    """Copy the benchmark, add one configuration, one traffic mix and one
    per-layer metric as new files plus manifest entries, and see the
    harness find and run them with no other file edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path / "chipbench")
    data = pathlib.Path(__file__).resolve().parent / "data"
    home = tmp_path / "chipbench"
    shutil.copy(data / "tiny_config.json", home / "configs" / "tiny.json")
    shutil.copy(data / "tiny_ingest.json", home / "traffic" / "burst.json")
    (home / "metrics" / "ingest.corrections.py").write_text(
        "def read(obs):\n    return obs.counters.get('corrections_acked')\n")
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "tiny", "source": "test", "file": "chipbench/configs/"
         "tiny.json", "reduced": [], "why": "test"})
    manifest["workloads"].append(
        {"name": "tiny.burst", "config": "tiny", "traffic": "burst",
         "chips": 1, "why": "test"})
    rate = next(m for m in manifest["end_to_end"]
                if m["name"] == "ingest_rows_per_s")
    rate["workloads"].append("tiny.burst")
    manifest["per_layer"].append(
        {"name": "ingest.corrections", "unit": "batches", "better": "lower",
         "source": "program_counter", "layer": "loader",
         "moves": "ingest_rows_per_s", "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = harness.find_cell("tiny.burst", root=tmp_path)
    assert cell.config["n_flights"] == 8192 and cell.home == home
    assert {m["name"] for m in cell.per_layer} == {"ingest.corrections"}
    result = harness.run_cell(cell, 5, 1.0, True, log=lambda line: None)
    assert result["correct"]
    # no device plane in a CPU trace: only the counter's reader reads
    assert set(result["metrics"]) == {"ingest.corrections"}
    after = _digest(home)
    assert {k: v for k, v in after.items() if k in before} == before
