"""Dispatch accounting, spans, compile seconds and program stages for the
online ingest AND query hot paths.

The single-dispatch claim of the fused pipelines ("one compiled program
per steady-state batch; one compiled program per uncached query") is
load-bearing: every extra launch is a host round-trip that serializes the
stream. jax 0.4.x executes jitted calls through a C++ fastpath that no
python-level hook observes, so the counter here instruments the call sites
we own instead: every compiled entry point of the engine hot paths is
wrapped with :func:`counted_jit`, which bumps a process-global counter on
each invocation of the compiled callable.

Scope: the counter sees every program launch issued through a
``counted_jit``-wrapped callable (all of ``repro.core.fused`` — ingest,
eviction, query and row-lookup programs — ``repro.core.online``'s planner
helpers, and the cached build/rollup programs). Launches can additionally
be LABELED (``counted_jit(fn, label="query")``) so tests can assert on one
entry-point family — e.g. "a cached ``ate()`` issues zero dispatches, an
uncached one exactly one". It does not see eager ``jnp`` operations — the
fused pipelines are written so their steady-state paths perform none
(pure-numpy host logic on fetched verdicts only), with ONE documented
exception: a batch whose row count is not already a power-of-two bucket
pays per-column eager ``jnp.pad`` copies before the ingest program
(``online.OnlineEngine._bucket_pad`` — async, no host sync, skipped
entirely for bucket-sized batches). ``tests/test_online_fused.py``
additionally asserts the jit trace cache stays cold (no retrace) across
steady-state ingests.

Spans (:func:`span`) mark the layer boundaries of the host path —
``durable.*``, ``wal.*``, ``engine.*``. They are recorded exactly while a
``jax.profiler`` session records: each is then a ``TraceAnnotation`` on
the trace's host plane (on the clock of the device ops) and one record in
memory (:func:`spans`), with its parent and the WAL sequence number of
the batch it served. With no session running a span costs one
``TraceAnnotation.is_enabled()`` check and keeps nothing.

Counters of the recorder (:func:`count`, :func:`counters`) are kept
on the same terms, where the work happens: ``wal.records`` and
``wal.bytes`` (records a log appended itself), ``wal.fsyncs``,
``ingest.redispatches`` (growth or delta-overflow re-dispatches of one
batch), ``ingest.resort_merges`` (views of a committed batch that took
the re-sort branch of the fused ingest program). Compile seconds
(:func:`compile_seconds`) come from a ``jax.monitoring`` listener
registered at import.

The fused ingest program names its stages with :func:`stage`
(``jax.named_scope``, one of :data:`INGEST_STAGES`);
:func:`op_stages` maps the compiled program's instruction names, which
are the names of the device ops in a profiler trace, to those stages.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import re
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
from jax.profiler import TraceAnnotation

_state = threading.local()


def _counter() -> list:
    if not hasattr(_state, "count"):
        _state.count = [0]
    return _state.count


def _labels() -> dict:
    if not hasattr(_state, "labels"):
        _state.labels = {}
    return _state.labels


def dispatch_count(label: Optional[str] = None) -> int:
    """Total compiled-program launches observed so far (this thread).

    ``label`` restricts the count to launches issued through
    ``counted_jit(..., label=label)`` wrappers (e.g. ``"query"`` for the
    fused query / row-lookup programs)."""
    if label is None:
        return _counter()[0]
    return _labels().get(label, 0)


def record_dispatch(n: int = 1, label: Optional[str] = None) -> None:
    """Manually account ``n`` launches (for call sites that cannot wrap)."""
    _counter()[0] += n
    if label is not None:
        lab = _labels()
        lab[label] = lab.get(label, 0) + n


def dispatch_counts() -> dict:
    """Snapshot of every labeled counter (label -> launches, this
    thread). The unlabeled total is :func:`dispatch_count`."""
    return dict(_labels())


def _batches() -> dict:
    if not hasattr(_state, "batches"):
        _state.batches = {}
    return _state.batches


def record_batch(n_items: int, label: str = "query") -> None:
    """Account ``n_items`` logical requests served by ONE batched launch
    of the ``label`` family — e.g. a batched query program answering B
    heterogeneous specs in one dispatch. Lets tests and benchmarks read
    requests-per-dispatch directly instead of inferring it."""
    b = _batches()
    b[label] = b.get(label, 0) + int(n_items)


def batched_served(label: str = "query") -> int:
    """Total logical requests served through batched launches of the
    ``label`` family (this thread); pairs with ``dispatch_count(label)``
    to give the amortization ratio of the batched query path."""
    return _batches().get(label, 0)


def _syncs() -> dict:
    if not hasattr(_state, "syncs"):
        _state.syncs = {None: 0}
    return _state.syncs


def record_host_sync(n: int = 1, label: Optional[str] = None) -> None:
    """Account ``n`` host synchronizations (device->host fetches that block
    the python thread on device results). The fused ingest hot path claims
    ZERO of these between a dispatch and its commit point; the overlap
    benches and the jaxpr audit read this counter to prove it, because
    ``jax.transfer_guard("disallow")`` only intercepts IMPLICIT transfers —
    an explicit ``jax.device_get`` sails straight through the guard."""
    s = _syncs()
    s[None] += n
    if label is not None:
        s[label] = s.get(label, 0) + n


def host_sync_count(label: Optional[str] = None) -> int:
    """Total host syncs accounted so far (this thread), optionally
    restricted to one ``label`` family (e.g. ``"commit"``, ``"query"``)."""
    return _syncs().get(label, 0)


@contextlib.contextmanager
def count_host_syncs(label: Optional[str] = None):
    """Context manager yielding a zero-based live host-sync counter:

    >>> with count_host_syncs() as n:
    ...     eng.ingest(batch)          # overlap mode: dispatch only
    >>> assert n() == 0                # verdicts are checked at commit()
    """
    start = host_sync_count(label)
    yield lambda: host_sync_count(label) - start


def device_fetch(tree, label: Optional[str] = None):
    """``jax.device_get`` with host-sync accounting — the ONLY way engine
    code is allowed to pull device values to the host (contract rule
    ZQL007 treats it as a sync call like ``jax.device_get`` itself).
    Routing every fetch through here lets the audit assert "zero host
    syncs between ingest dispatch and commit" as a counted fact rather
    than an unobservable claim."""
    record_host_sync(1, label=label)
    return jax.device_get(tree)


# Background-thread accounting. Dispatch/sync counters above are
# thread-local on purpose (each test thread sees only its own launches),
# which makes them blind to work done OFF the engine thread — e.g. the
# AsyncSaver retrying a checkpoint write in its writer thread. Events are
# the process-global, lock-protected complement for exactly those.
_events: dict = {}
_events_lock = threading.Lock()


def record_event(name: str, n: int = 1) -> None:
    """Account ``n`` occurrences of a named process-global event (safe to
    call from any thread; e.g. ``"ckpt_save_retry"`` from the AsyncSaver
    writer thread)."""
    with _events_lock:
        _events[name] = _events.get(name, 0) + n


def event_count(name: str) -> int:
    """Total process-global occurrences of ``name`` recorded so far."""
    with _events_lock:
        return _events.get(name, 0)


def hot_path(fn: Callable) -> Callable:
    """Marker for traced hot-path bodies: ``fn`` runs INSIDE a compiled
    program (a fused-pipeline body, a shard_map shard body, a Pallas
    kernel wrapper), so it must stay free of host synchronization —
    ``jax.device_get``, ``np.asarray``/``np.array``, ``.block_until_ready``,
    ``float()/int()/bool()`` on traced values would either fail under jit
    or silently serialize the stream when the body is also callable
    eagerly. A no-op at runtime; the static contract checker
    (``repro.analysis``, rule ZQL002) enforces the restriction on every
    function carrying this marker or wrapped by :func:`counted_jit`."""
    fn.__hot_path__ = True
    return fn


def counted_jit(fn: Callable = None, label: Optional[str] = None,
                **jit_kwargs) -> Callable:
    """``jax.jit`` that bumps the dispatch counter once per call.

    Drop-in replacement: ``counted_jit(f, static_argnames=...)`` or as a
    decorator. ``label`` additionally attributes the launch to a named
    entry-point family (see :func:`dispatch_count`). The wrapper preserves
    the jitted callable's AOT/trace attributes that the engines rely on
    (``_cache_size`` for the no-retrace assertion). While a profiler
    session records, a launch also remembers its abstract signature for
    :func:`op_stages`."""
    def wrap(f):
        jitted = jax.jit(f, **jit_kwargs)

        @functools.wraps(f)
        def call(*args, **kwargs):
            record_dispatch(1, label=label)
            if _recording():
                _remember_call(label, jitted, args, kwargs)
            return jitted(*args, **kwargs)

        call._jitted = jitted
        call._cache_size = jitted._cache_size
        call.lower = jitted.lower
        return call

    return wrap if fn is None else wrap(fn)


@contextlib.contextmanager
def count_dispatches(label: Optional[str] = None):
    """Context manager yielding a zero-based live counter:

    >>> with count_dispatches() as n:
    ...     eng.ingest(batch)
    >>> assert n() == 1

    ``label`` restricts the live counter to one entry-point family:

    >>> with count_dispatches(label="query") as n:
    ...     eng.ate("t")
    >>> assert n() == 1
    """
    start = dispatch_count(label)
    yield lambda: dispatch_count(label) - start


# ------------------------------------------------------------------ spans
#: the stages of the fused ingest program (``jax.named_scope`` names; see
#: :func:`stage`), in program order
INGEST_STAGES = ("build", "rollup", "probe", "branch", "merge_fast",
                 "resort", "relocate", "touch_remap", "overlap", "gate",
                 "stream")

#: HLO opcodes whose device time nests the time of the ops they run
CONTROL_OPCODES = frozenset(("conditional", "while", "call"))

_recording = TraceAnnotation.is_enabled
_ids = itertools.count(1)
_spans_lock = threading.Lock()
_records: List["_Open"] = []


@dataclasses.dataclass(frozen=True)
class Span:
    """One recorded span. ``seq`` is the WAL sequence number of the batch
    the span served (shared by every span of one durable ingest or
    commit); times are ``time.perf_counter_ns``."""
    name: str
    id: int
    parent: Optional[int]
    seq: Optional[int]
    start_ns: int
    end_ns: int
    attrs: dict


class _Null:
    """The span while no profiler session records: does nothing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL = _Null()


def _open_stack() -> list:
    if not hasattr(_state, "open"):
        _state.open = []
    return _state.open


class _Open:
    """A span being recorded: a ``TraceAnnotation`` plus its record."""

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs
        self.id = next(_ids)
        with _events_lock:
            if _compile_at_start[0] is None:
                _compile_at_start[0] = _compile_s[0]
        stack = _open_stack()
        up = stack[-1] if stack else None
        self.parent = None if up is None else up.id
        # one request cell per root, shared by its descendants, so a seq
        # set on the root after they closed still reaches them
        self.request = ({"seq": attrs.get("seq")} if up is None
                        else up.request)

    def __enter__(self):
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        _open_stack().append(self)
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        _open_stack().pop()
        _keep(self)
        return False

    def set(self, **attrs) -> None:
        """Add attributes once known (``seq`` after the WAL append)."""
        self.attrs.update(attrs)
        if "seq" in attrs and self.parent is None:
            self.request["seq"] = attrs["seq"]


def span(name: str, **attrs):
    """Context manager marking one layer boundary of the host path.

    While a ``jax.profiler`` session records it opens a
    ``jax.profiler.TraceAnnotation(name)`` and keeps a :class:`Span`;
    otherwise it returns a shared no-op. ``attrs`` (``seq``, ``rows``,
    ...) are kept with the record; ``.set(**attrs)`` on the entered span
    adds more."""
    if not _recording():
        return _NULL
    return _Open(name, attrs)


def _keep(rec: _Open) -> None:
    with _spans_lock:
        _records.append(rec)


def _record(name: str, start_ns: int, end_ns: int) -> None:
    """Keep a span that ended already, as a child of the open span."""
    rec = _Open(name, {})
    rec.start, rec.end = start_ns, end_ns
    _keep(rec)


def spans() -> List[Span]:
    """Every span recorded so far (all threads), in order of start."""
    with _spans_lock:
        recs = list(_records)
    out = [Span(r.name, r.id, r.parent, r.request["seq"], r.start, r.end,
                dict(r.attrs)) for r in recs]
    return sorted(out, key=lambda sp: (sp.start_ns, sp.id))


def clear_spans() -> None:
    """Forget every span, counter and call signature the recorder kept."""
    with _spans_lock:
        _records.clear()
        _counts.clear()
        _calls.clear()
        _stage_maps.clear()
    with _events_lock:
        _compile_at_start[0] = None


_counts: Dict[str, int] = {}


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the recorder's counter ``name`` while a profiler
    session records (any thread); otherwise do nothing."""
    if _recording():
        with _spans_lock:
            _counts[name] = _counts.get(name, 0) + n


def counters() -> Dict[str, int]:
    """Snapshot of the recorder's counters (name -> total)."""
    with _spans_lock:
        return dict(_counts)


def self_times(recs: Sequence[Span]) -> Dict[int, int]:
    """Span id -> self time in ns: its duration minus the part of it that
    its children cover."""
    children: Dict[int, list] = {}
    for sp in recs:
        if sp.parent is not None:
            children.setdefault(sp.parent, []).append(
                (sp.start_ns, sp.end_ns))
    out = {}
    for sp in recs:
        covered, reach = 0, sp.start_ns
        for s, e in sorted(children.get(sp.id, ())):
            s, e = max(s, reach), min(e, sp.end_ns)
            if e > s:
                covered += e - s
                reach = e
        out[sp.id] = sp.end_ns - sp.start_ns - covered
    return out


# -------------------------------------------------------- compile seconds
#: the ``jax.monitoring`` events of one compilation: jaxpr tracing,
#: lowering to MLIR, and the backend compile (which includes a persistent
#: compilation-cache retrieval)
COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration"))

_compile_s = [0.0]
# compile seconds when the recorder first recorded (None: not yet)
_compile_at_start: List[Optional[float]] = [None]
# time.time() -> perf_counter_ns, for compile spans
_clock_offset_ns = time.perf_counter_ns() - time.time_ns()


def compile_seconds() -> float:
    """Seconds spent compiling (tracing, lowering, backend compile or
    cache load) since this module was imported, outermost events only:
    the trace of a jitted function nested in another counts once."""
    with _events_lock:
        return _compile_s[0]


def compile_seconds_before_recording() -> Optional[float]:
    """:func:`compile_seconds` at the first span the recorder kept since
    import or :func:`clear_spans`: the compiles of everything the process
    ran before its first recorded profiler session (in a traced benchmark
    run, the set-up), and none of those inside or after it. None while
    nothing was recorded."""
    with _events_lock:
        return _compile_at_start[0]


def _compile_started(event: str, value, **kwargs) -> None:
    if event in COMPILE_EVENTS:
        _state.compile_depth = getattr(_state, "compile_depth", 0) + 1


def _compile_ended(event: str, start: float, end: float, **kwargs) -> None:
    if event not in COMPILE_EVENTS:
        return
    depth = max(getattr(_state, "compile_depth", 1) - 1, 0)
    _state.compile_depth = depth
    if depth or getattr(_state, "uncounted", False):
        return
    if _recording():
        _record("compile", int(start * 1e9) + _clock_offset_ns,
                int(end * 1e9) + _clock_offset_ns)
    with _events_lock:
        _compile_s[0] += end - start


jax.monitoring.register_scalar_listener(_compile_started)
jax.monitoring.register_event_time_span_listener(_compile_ended)


# --------------------------------------------------------- program stages
def stage(name: str):
    """``jax.named_scope`` of one stage of the fused ingest program."""
    if name not in INGEST_STAGES:
        raise ValueError(f"unknown ingest stage {name!r}")
    return jax.named_scope(name)


class OpStage(NamedTuple):
    """What :func:`op_stages` knows of one compiled instruction: its
    innermost known stage (None: none) and whether it is a leaf (control
    flow nests its ops' device time)."""
    stage: Optional[str]
    leaf: bool


# label -> {signature key: (jitted, treedef, abstract leaves)}, for every
# program launched while recording: the names of one label's programs
# are known only beside those of every other program that ran
_calls: Dict[Optional[str], dict] = {}
_stage_maps: Dict[str, Dict[str, OpStage]] = {}

#: the largest share of a label's leaf-op device time that may map to no
#: stage for :func:`stage_ms_per_batch` to read a stage
UNMAPPED_SHARE = 0.05


def _abstract(x):
    """What the jit call saw of ``x``: shape, dtype, weak type, and the
    sharding only of a committed array, as the call took no other (the
    same lowering, so a hit in the compilation caches)."""
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    aval = getattr(x, "aval", None)
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype,
        sharding=x.sharding if getattr(x, "committed", False) else None,
        weak_type=bool(getattr(aval, "weak_type", False)))


def _remember_call(label: Optional[str], jitted, args, kwargs) -> None:
    leaves, tree = jax.tree.flatten((args, kwargs))
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return          # called inside another program: no launch of its own
    sig = tuple(_abstract(x) for x in leaves)
    key = (id(jitted), tree, sig)
    with _spans_lock:
        calls = _calls.setdefault(label, {})
        if key not in calls:
            calls[key] = (jitted, tree, sig)
            _stage_maps.clear()


_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*.*?\s"
                     r"([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_KNOWN_STAGES = frozenset(INGEST_STAGES)


def hlo_op_stages(text: str) -> Dict[str, OpStage]:
    """Instruction name -> :class:`OpStage` of one compiled HLO module's
    text: the innermost scope of its ``op_name`` metadata that is one of
    :data:`INGEST_STAGES` (a scope under a transform reads
    ``vmap(resort)``)."""
    out = {}
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        name, opcode = m.groups()
        meta = _OP_NAME.search(line)
        where = None
        if meta is not None:
            scopes = (part.rsplit("(", 1)[-1].rstrip(")")
                      for part in reversed(meta.group(1).split("/")))
            where = next((sc for sc in scopes if sc in _KNOWN_STAGES), None)
        out[name] = OpStage(where, opcode not in CONTROL_OPCODES)
    return out


@contextlib.contextmanager
def _uncounted():
    """Compiles made here are the caller's, not the program's."""
    before = getattr(_state, "uncounted", False)
    _state.uncounted = True
    try:
        yield
    finally:
        _state.uncounted = before


def op_stages(label: str) -> Dict[str, OpStage]:
    """Instruction name -> :class:`OpStage` of the programs of ``label``
    launched while a profiler session recorded.

    Each distinct call signature of every program launched while
    recording is lowered and compiled again (a hit in the in-process or
    persistent compilation cache, not counted by
    :func:`compile_seconds`) and its ``op_name`` metadata read. A trace
    names device ops by bare instruction name, which repeats across
    programs: a name that a program of another label also has, or that
    two programs of ``label`` place in different stages, maps to
    ``OpStage(None, ...)`` (no stage), so its time shows as unmapped
    rather than under a stage it may not belong to. Ops of programs not
    launched through :func:`counted_jit` are not seen. The stages are
    those of the executable that ran: the persistent compilation cache
    keys a program without its scope names, so an entry compiled from
    older source of the same computation keeps that source's stages."""
    with _spans_lock:
        cached = _stage_maps.get(label)
        calls = [(lab, call) for lab, by_sig in _calls.items()
                 for call in by_sig.values()]
    if cached is not None:
        return cached
    found: Dict[str, set] = {}
    with _uncounted():
        for lab, (jitted, tree, sig) in calls:
            args, kwargs = jax.tree.unflatten(tree, sig)
            text = jitted.lower(*args, **kwargs).compile().as_text()
            for name, op in hlo_op_stages(text).items():
                found.setdefault(name, set()).add((lab, op))
    out: Dict[str, OpStage] = {}
    for name, seen in found.items():
        if all(lab != label for lab, _ in seen):
            continue
        if len(seen) == 1:
            (_, out[name]), = seen
        else:
            out[name] = OpStage(None, any(op.leaf for _, op in seen))
    with _spans_lock:
        _stage_maps[label] = out
    return out


def stage_seconds(op_seconds, label: str = "ingest"
                  ) -> Dict[Optional[str], float]:
    """Device seconds per stage from ``(instruction name, seconds)``
    pairs of a trace (e.g. the benchmark's ``top_ops``): leaf ops of the
    ``label`` programs only; key None collects leaf ops with no stage.
    Empty when no op of the pairs is an op of those programs."""
    ops = op_stages(label)
    out: Dict[Optional[str], float] = {}
    for name, secs in op_seconds:
        op = ops.get(name)
        if op is not None and op.leaf:
            out[op.stage] = out.get(op.stage, 0.0) + secs
    return out


def stage_ms_per_batch(op_seconds, n_batches, stages: Sequence[str],
                       label: str = "ingest") -> Optional[float]:
    """Device ms per batch of ``stages`` of the ``label`` programs, from
    ``(instruction name, seconds)`` pairs of a trace of ``n_batches``
    batches. None where no op of the pairs is an op of those programs,
    or where more than :data:`UNMAPPED_SHARE` of their leaf-op time maps
    to no stage (the stages then cannot be told apart)."""
    if not n_batches:
        return None
    secs = stage_seconds(op_seconds, label)
    total = sum(secs.values())
    if total <= 0 or secs.get(None, 0.0) > UNMAPPED_SHARE * total:
        return None
    return 1e3 * sum(secs.get(s, 0.0) for s in stages) / n_batches
