"""Host spans, the profiler, and the reduction from a trace to numbers.

The benchmark marks what the host is doing with :class:`Spans` (a
``jax.profiler.TraceAnnotation`` while a trace is recorded, nothing
otherwise); its spans follow one another and never nest, but for the
``window`` span around them all. A ``--trace 1`` run records one profiler trace of its window
and :func:`reduce` turns it into:

* ``busy_ns``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped
  to the window and averaged over the devices used;
* ``window_ns``: the length of the host span named ``window``;
* ``modules``: every program execution on a device (``XLA Modules``) in
  the window, with the host span it ran under;
* ``top_ops``: device time per HLO instruction name;
* ``gaps``: every idle interval of the device in the window, named by the
  benchmark span the host was in at the gap's midpoint.

Device and host events share the trace's clock to within about a
millisecond (the device's is offset), far less than any span here.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

#: the benchmark's own host spans
SPANS = ("batch_build", "journal_dispatch", "commit", "wave_step",
         "submit", "generator_sleep")
WINDOW = "window"

Interval = Tuple[float, float]


class Spans:
    """Span factory: real trace annotations only while tracing."""

    def __init__(self, enabled: bool):
        self.enabled = enabled

    def __call__(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def recording(log_dir: str) -> Iterator[None]:
    """Record a profiler trace (no Python function tracing) to
    ``log_dir`` around the block."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def trace_file(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{files}")
    return files[0]


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    """What the reduction reads of one ``.xplane.pb``."""
    ops: Dict[str, List[Event]]       # device plane -> XLA Ops
    modules: Dict[str, List[Event]]   # device plane -> XLA Modules
    spans: List[Event]                # benchmark host spans

    @classmethod
    def load(cls, path: str) -> "Trace":
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        ops: Dict[str, List[Event]] = {}
        modules: Dict[str, List[Event]] = {}
        spans: List[Event] = []
        names = set(SPANS) | {WINDOW}
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    target = {"XLA Ops": ops,
                              "XLA Modules": modules}.get(line.name)
                    if target is not None:
                        target[plane.name] = [
                            Event(ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                            for ev in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [Event(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns)
                              for ev in line.events if ev.name in names]
        return cls(ops=ops, modules=modules, spans=spans)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(hlo: str) -> str:
    """``%sort.6 = (f32[...]) sort(...)`` -> ``sort.6``."""
    head = hlo.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


@dataclasses.dataclass
class Reduced:
    window_ns: float
    busy_ns: float
    modules: List[Tuple[Event, Optional[str]]]
    top_ops: List[Tuple[str, float]]
    gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def module_ns(self, under: str) -> float:
        """Device time of the programs that ran under host span
        ``under``."""
        return sum(ev.end - ev.start for ev, sp in self.modules
                   if sp == under)


class _SpanIndex:
    """The benchmark span open at a time: spans sorted by start; where
    two overlap (widened by the clock slack), the shorter wins."""

    def __init__(self, spans: Sequence[Event], slack_ns: float = 0.0):
        self.spans = sorted((Event(sp.name, sp.start - slack_ns,
                                   sp.end + slack_ns) for sp in spans),
                            key=lambda sp: sp.start)
        self.starts = [sp.start for sp in self.spans]

    def at(self, t: float) -> Optional[str]:
        i = bisect.bisect_right(self.starts, t)
        hits = [sp for sp in self.spans[max(0, i - 2):i] if sp.end >= t]
        if not hits:
            return None
        return min(hits, key=lambda sp: sp.end - sp.start).name


def reduce(trace: Trace, slack_ns: float = 2e6) -> Reduced:
    """Reduce a trace to the window's device numbers (module docstring).
    ``slack_ns`` widens host spans when a program is matched to the span
    it ran under, to cover the offset between the two clocks."""
    windows = [sp for sp in trace.spans if sp.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one '{WINDOW}' span, found "
                           f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    inner = [sp for sp in trace.spans if sp.name != WINDOW]
    host = _SpanIndex(inner)
    busy, gaps = [], []
    per_op: Dict[str, float] = {}
    for evs in trace.ops.values():
        live = union(clip([(e.start, e.end) for e in evs], lo, hi))
        busy.append(sum(e - s for s, e in live))
        edges = [lo] + [x for iv in live for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((host.at((s + e) / 2) or "other",
                             (e - s) / 1e9))
        for ev in evs:
            if ev.end > lo and ev.start < hi:
                name = op_name(ev.name)
                per_op[name] = per_op.get(name, 0.0) + (
                    min(ev.end, hi) - max(ev.start, lo)) / 1e9
    modules = []
    wide = _SpanIndex(inner, slack_ns)
    for evs in trace.modules.values():
        for ev in evs:
            if ev.end > lo and ev.start < hi:
                modules.append((ev, wide.at((ev.start + ev.end) / 2)))
    n_dev = max(len(busy), 1)
    return Reduced(
        window_ns=hi - lo, busy_ns=sum(busy) / n_dev, modules=modules,
        top_ops=sorted(per_op.items(), key=lambda kv: -kv[1]),
        gaps=sorted(gaps, key=lambda g: -g[1]))
