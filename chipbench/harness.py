"""The benchmark harness: one run of one cell, driven by data.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``chipbench/configs/<config>.json``: the deployment (schema, scale,
  guarantee, what was cut and assumed);
* ``chipbench/traffic/<traffic>.json``: the mix; its ``driver`` key names
  ``chipbench/drivers/<driver>.py``, the general generator of that kind
  of traffic, which reads every parameter from the file;
* ``chipbench/metrics/<metric>.py``: a reader ``read(obs)`` that returns
  the metric from a traced run's :class:`Observation`, or None where it
  finds nothing to read.

A driver module defines ``Driver(ctx)`` with ``setup()``, ``window(
seconds)``, ``end_to_end()`` (metric name -> value), ``counters()``
(what the window counted, for the readers) and ``check()`` (a
:class:`Checked`), and ``close()``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import tempfile
import time
from typing import Callable, Dict, List, Mapping, Optional, Tuple

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    """Import ``path`` as a module named ``name`` (file names here may
    hold dots, as metric names do)."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the manifest's metrics this cell reports
    per_layer: List[dict]
    home: pathlib.Path = HERE   # the directory holding drivers/, metrics/


def _reports(metric: Mapping, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def find_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``, with its
    configuration and traffic files loaded."""
    manifest = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg = load_json(root / configs[w["config"]]["file"])
    home = root / "chipbench"
    traffic = load_json(home / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in manifest["end_to_end"] if _reports(m, workload)]
    # per-layer metrics moving an end-to-end metric this cell reports
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if _reports(m, workload) and m["moves"] in reported]
    return Cell(workload, int(w["chips"]), cfg, traffic, e2e, per_layer,
                home)


def require_device(chips: int) -> Dict:
    """The device tag of the TPUs JAX sees; :class:`NoChip` if it sees
    no TPU or fewer than ``chips``. Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    tag = device_tag(devs[:chips])
    if tag["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {tag['platform']!r}); "
                     "the benchmark runs on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devs)}")
    return tag


def device_tag(devs) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


@dataclasses.dataclass
class Context:
    """What a driver gets: its cell's files, the seed, a scratch
    directory (under ``$TMPDIR``), a log and the host-span factory."""
    config: dict
    traffic: dict
    seed: int
    workdir: str
    log: Callable[[str], None]
    span: Callable


@dataclasses.dataclass
class Checked:
    """A run's comparison with the reference: operations attempted and
    failed in the window, and every number compared -> (value, limit);
    the run is correct when each value is at most its limit."""
    attempted: int
    failed: int
    compared: Dict[str, Tuple[float, float]]

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.compared.values())


@dataclasses.dataclass
class Observation:
    """What a per-layer metric reads: the reduced trace of the window
    and the window's counters."""
    trace: "object"
    counters: Dict[str, float]


def log_stderr(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def memory_peak(devs) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return max(peaks)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: Optional[Dict] = None, log=log_stderr,
             age: Callable[[], float] = process_age_s) -> Dict:
    """Set up, measure and check one run of ``cell``; returns the result
    line's object. ``device`` is the tag :func:`require_device` gave."""
    import jax

    from chipbench import tracing
    devs = jax.devices()[:cell.chips]
    device = dict(device or device_tag(devs))
    driver_mod = load_module(
        cell.home / "drivers" / f"{cell.traffic['driver']}.py",
        f"chipbench_driver_{cell.traffic['driver']}")
    with tempfile.TemporaryDirectory(prefix="chipbench_") as work:
        spans = tracing.Spans(enabled=trace)
        ctx = Context(cell.config, cell.traffic, seed, work, log, spans)
        drv = driver_mod.Driver(ctx)
        try:
            drv.setup()
            setup_s = age()
            log(f"setup: {setup_s:.3f} s")
            reduced = None
            if trace:
                log_dir = os.path.join(work, "trace")
                with tracing.recording(log_dir):
                    with spans(tracing.WINDOW):
                        drv.window(seconds)
                t = time.perf_counter()
                reduced = tracing.reduce(tracing.Trace.load(
                    tracing.trace_file(log_dir)))
                log(f"trace reduced in {time.perf_counter() - t:.1f} s")
            else:
                drv.window(seconds)
            device["memory_peak_bytes"] = memory_peak(devs)
            e2e = drv.end_to_end()
            counters = drv.counters()
            checked = drv.check()
        finally:
            drv.close()
    metrics: Dict[str, Dict] = {}
    if trace:
        obs = Observation(reduced, counters)
        for m in cell.per_layer:
            reader = load_module(cell.home / "metrics" / f"{m['name']}.py",
                                 "chipbench_metric_" + m["name"].replace(
                                     ".", "_"))
            value = reader.read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_ns / 1e9
        device["window_s"] = reduced.window_ns / 1e9
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": checked.correct, "attempted": checked.attempted,
              "failed": checked.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.top_ops[:10]],
            "idle_gaps": [[n, s] for n, s in reduced.gaps[:10]]}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in checked.compared.items()}
    return result
