"""The online engine's main path at FLIGHTDELAY scale, as importable phases.

``chip_smoke.py`` (repo root) runs these on the chip; the tests run them
on the CPU at :data:`TINY`. One run drives what a user of the engine
calls, in order:

1. the paper's §5 FLIGHTDELAY relation from ``data/flightgen.py``:
   flights joined to hourly weather on the device (``data/join.fk_join``),
   three weather treatments, each with its own covariate set;
2. ``DurableEngine(OnlineEngine(...))`` with default options: one large
   seed batch, then a stream of equal batches, each journaled and
   fsynced, with one checkpoint half way;
3. uncached ``ate()`` per treatment, overall and per airport, and one
   ``ate_batch`` wave of distinct specs;
4. close, ``DurableEngine.recover`` a fresh engine from the checkpoint
   plus the WAL tail, and ask the same questions again.

Checks (any failure raises :class:`SmokeCheckFailed`): every estimate
against the float64 host reference (``core/oracle.py``) within a tolerance
derived from f32 accumulation (:func:`tolerance`); each wave slot bitwise
equal to the sequential ``ate()``; the recovered engine bitwise equal to
the live one; one dispatch per steady ingest and per wave.

:func:`run_mesh` is the four-chip phase: the same stream into
``PartitionedOnlineEngine`` over a 4-device mesh, a row-sharded
``OnlineEngine`` on the same mesh, and a one-device ``OnlineEngine``, all
in one process; the three must agree bitwise and with the reference.

Outcomes are whole minutes (``dep_delay`` rounded on the device), as the
DOT on-time table reports departure delay. Every group sum is then an
integer below 2^24 (checked), so f32 group stats are exact in any order:
that is the precondition of the engine's cross-layout bit-identity
contract, and it confines the reference tolerance to the estimator's own
f32 arithmetic.
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.core  # noqa: F401  (imported before repro.data, which needs it)
from repro.core import (CoarsenSpec, DurableEngine, OnlineEngine,
                        PartitionedOnlineEngine, oracle)
from repro.core.serving import QuerySpec
from repro.data import flightgen
from repro.data.columnar import Table
from repro.data.join import fk_join
from repro.launch.trace import count_dispatches

Log = Callable[[str], None]


class SmokeCheckFailed(RuntimeError):
    """A check of the smoke run failed; the run must not report ok."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckFailed(what)


@dataclasses.dataclass(frozen=True)
class Size:
    """One run's scale. Widths (schema, coarsening, treatments) never
    change between sizes; only row counts, key cardinalities and the
    number of batches and queries do."""
    n_flights: int
    n_airports: int
    n_carriers: int
    n_days: int
    seed_rows: int        # first ingest
    batch_rows: int       # each streamed batch after it
    n_batches: int
    ckpt_after: int       # checkpoint after this many streamed batches
    query_airports: int   # single-airport subpopulations asked per treatment
    wave: int             # distinct specs in the ate_batch wave
    # the mesh phase streams the same flights in ``mesh_batches`` equal
    # batches into engines sized up front (view and delta capacity), so
    # each engine compiles one ingest program and one wave program
    mesh_batches: int
    mesh_granule: int
    mesh_delta_granule: int
    mesh_wave: int


#: one chip: 2^23 flights over 64 airports, 16 carriers and a year of
#: hourly weather (560,640 rows); 2^22 seed rows + 64 x 2^16 streamed.
#: Views end near 1.5-2.3e5 groups and the base near 3.1e5, and a
#: 2^21-row batch holds fewer than 2^19 base groups: the mesh sizing
#: (2^19 view and delta slots) never grows
FULL = Size(n_flights=1 << 23, n_airports=64, n_carriers=16, n_days=365,
            seed_rows=1 << 22, batch_rows=1 << 16, n_batches=64,
            ckpt_after=32, query_airports=8, wave=256, mesh_batches=4,
            mesh_granule=1 << 19, mesh_delta_granule=1 << 19, mesh_wave=64)

#: the CPU rehearsal: same schema and path, a few thousand rows
TINY = Size(n_flights=1 << 14, n_airports=8, n_carriers=4, n_days=30,
            seed_rows=1 << 13, batch_rows=1 << 9, n_batches=16,
            ckpt_after=8, query_airports=3, wave=16, mesh_batches=4,
            mesh_granule=1 << 10, mesh_delta_granule=1 << 11, mesh_wave=16)

OUTCOME = "dep_delay"
SHARED = ("airport", "carrier", "traffic", "w_season")
COVARIATES = {
    "thunder": ("w_precipm", "w_wspdm"),
    "snow": ("w_tempm", "w_wspdm"),
    "highwind": ("w_precipm", "w_tempm"),
}
WEATHER_RANGES = {"w_precipm": (0, 3), "w_wspdm": (0, 80),
                  "w_tempm": (-20, 40)}
QUERY_DIMS = ("airport",)
HOUR_CARD = 1 << 17            # join-key width of the hour column

#: f32 unit roundoff
EPS32 = 2.0 ** -24


def build_specs(size: Size) -> Dict[str, CoarsenSpec]:
    """Coarsening of every covariate: airport and carrier categorical,
    traffic in 8 bins, season in 4, each weather covariate in 5."""
    specs = {
        "airport": CoarsenSpec.categorical(size.n_airports),
        "carrier": CoarsenSpec.categorical(size.n_carriers),
        "traffic": CoarsenSpec.equal_width(0, 40, 8),
        "w_season": CoarsenSpec.equal_width(0, 1, 4),
    }
    for name, (lo, hi) in WEATHER_RANGES.items():
        specs[name] = CoarsenSpec.equal_width(lo, hi, 5)
    return specs


def treatments() -> Dict[str, Tuple[str, ...]]:
    return {t: SHARED + cov for t, cov in COVARIATES.items()}


def engine_columns() -> Tuple[str, ...]:
    return (*SHARED, *WEATHER_RANGES, *COVARIATES, OUTCOME)


def make_engine(cls, size: Size, **kw):
    """An engine of the smoke's schema with default options."""
    return cls(build_specs(size), treatments(), OUTCOME,
               query_dims=QUERY_DIMS, **kw)


def device_tag() -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(n_devices: int = 1) -> Dict:
    """The device tag, or SmokeCheckFailed when JAX sees no TPU (or
    fewer than ``n_devices`` of them): the smoke never falls back."""
    tag = device_tag()
    check(tag["platform"] == "tpu",
          f"JAX found no TPU (platform {tag['platform']!r}); the smoke "
          "runs on the chip only")
    check(tag["count"] >= n_devices,
          f"{n_devices} TPU devices needed, JAX sees {tag['count']}")
    return tag


# ----------------------------------------------------------------- data
@dataclasses.dataclass
class FlightStream:
    """The joined fact table: on the device (``table``) and its host copy
    (``host``, with ``_valid``) that batches and the reference read."""
    table: Table
    host: Dict[str, np.ndarray]
    n_weather: int

    @property
    def n_rows(self) -> int:
        return self.table.nrows

    def batch(self, start: int, stop: int) -> Table:
        cols = {k: v[start:stop] for k, v in self.host.items()
                if k != "_valid"}
        return Table.from_numpy(cols, self.host["_valid"][start:stop])

    def schedule(self, size: Size) -> List[Tuple[int, int]]:
        """(start, stop) of the seed batch, then every streamed batch."""
        out = [(0, size.seed_rows)]
        for i in range(size.n_batches):
            s = size.seed_rows + i * size.batch_rows
            out.append((s, s + size.batch_rows))
        check(out[-1][1] == size.n_flights,
              "the batch schedule must cover every flight exactly once")
        return out

    def equal_batches(self, n: int) -> List[Tuple[int, int]]:
        """(start, stop) of ``n`` equal batches covering every flight."""
        step = self.n_rows // n
        check(step * n == self.n_rows, f"{self.n_rows} rows do not split "
              f"into {n} equal batches")
        return [(i * step, (i + 1) * step) for i in range(n)]


def make_stream(size: Size, seed: int) -> FlightStream:
    """Generate FLIGHTDELAY from ``seed``, round delays to whole minutes
    and join flights to weather on the device."""
    data = flightgen.generate(n_flights=size.n_flights,
                              n_airports=size.n_airports,
                              n_carriers=size.n_carriers,
                              n_days=size.n_days, seed=seed)
    flights = data.flights.select(
        ["airport", "hour", "carrier", "traffic", OUTCOME, *COVARIATES])
    flights = flights.with_columns({OUTCOME: jnp.round(flights[OUTCOME])})
    weather = data.weather.select(
        ["airport", "hour", "season", "precipm", "wspdm", "tempm"])
    n_weather = weather.nrows
    del data
    joined = fk_join(flights, weather,
                     on={"airport": size.n_airports, "hour": HOUR_CARD},
                     prefix="w_").select(list(engine_columns()))
    host = joined.to_numpy()
    return FlightStream(table=joined, host=host, n_weather=n_weather)


def table_bytes(table: Table) -> int:
    return sum(int(v.nbytes) for v in table.columns.values()) + int(
        table.valid.nbytes)


# ------------------------------------------------------------ reference
def bucketize(host: Dict[str, np.ndarray], specs) -> Dict[str, np.ndarray]:
    """Host float32 coarsening with the engine's bucket rule."""
    out = {}
    for name, spec in specs.items():
        x = host[name]
        if spec.kind == "categorical":
            out[name] = np.clip(x.astype(np.int64), 0, spec.cardinality - 1)
        else:
            out[name] = oracle.coarsen_oracle(
                x.astype(np.float32),
                np.asarray(spec.cutpoints, np.float32)).astype(np.int64)
    return out


def reference(stream: FlightStream, size: Size) -> Dict[str, dict]:
    """Per-treatment float64 group tables of every ingested row."""
    specs = build_specs(size)
    buckets = bucketize(stream.host, specs)
    y = stream.host[OUTCOME]
    valid = stream.host["_valid"]
    return {t: oracle.cem_group_stats_oracle(
        {d: buckets[d] for d in dims}, stream.host[t], y, valid)
        for t, dims in treatments().items()}


def tolerance(scale: float, length: int) -> float:
    """Bound on |engine - reference| for one ATE/ATT.

    Group sums are exact (integer outcomes, every sum < 2^24, checked),
    so only the estimator's f32 arithmetic errs: per group the two arm
    means, their difference and the weight product (3 roundings relative
    to ``w (|mean_t| + |mean_c|)``), the pairwise fold over a vector of
    ``length`` slots (ceil(log2 length) roundings along any path) and the
    final division (1). To first order |err| <= u (ceil(log2 L) + 4)
    sum w (|m_t| + |m_c|) / sum w with u = 2^-24; ``scale`` is that
    weighted mean, and the factor 2 covers second-order terms."""
    levels = math.ceil(math.log2(max(length, 2)))
    return 2.0 * EPS32 * (levels + 4) * scale


def query_airports(size: Size, seed: int) -> List[int]:
    rng = np.random.default_rng(seed + 1)
    return sorted(int(a) for a in rng.choice(size.n_airports,
                                             size.query_airports,
                                             replace=False))


def single_queries(size: Size, seed: int) -> List[Tuple[str, Optional[dict]]]:
    """(treatment, subpopulation) of every uncached ``ate()``: per
    treatment, overall and each of the query airports."""
    subs = [None] + [{"airport": [a]} for a in query_airports(size, seed)]
    return [(t, s) for t in sorted(COVARIATES) for s in subs]


def wave_specs(size: Size, seed: int) -> List[QuerySpec]:
    """``size.wave`` DISTINCT specs (treatment x airport subset, estimand
    alternating): distinct ``(treatment, subpopulation)`` keys, since the
    engine collapses duplicates onto one slot."""
    rng = np.random.default_rng(seed + 2)
    tnames = sorted(COVARIATES)
    out, seen = [], set()
    while len(out) < size.wave:
        t = tnames[len(out) % len(tnames)]
        k = int(rng.integers(1, min(8, size.n_airports) + 1))
        sub = tuple(sorted(int(a) for a in rng.choice(size.n_airports, k,
                                                      replace=False)))
        if (t, sub) in seen:
            continue
        seen.add((t, sub))
        out.append(QuerySpec.make(t, {"airport": list(sub)},
                                  estimand=("ate", "att")[len(out) % 2]))
    return out


# ------------------------------------------------------------- checks
EST_FIELDS = ("ate", "att", "n_matched_treated", "n_matched_control",
              "n_groups", "variance")


def est_bits(est) -> Tuple[bytes, ...]:
    return tuple(np.asarray(getattr(est, f)).tobytes() for f in EST_FIELDS)


def check_against_reference(ests: Dict, ref: Dict[str, dict],
                            lengths: Dict[str, int], log: Log) -> float:
    """Compare every (treatment, subpopulation) estimate with the
    reference; returns the largest error over tolerance seen."""
    worst, worst_err = 0.0, 0.0
    for (t, sub), est in ests.items():
        want = oracle.ate_att_oracle(ref[t], _sub(sub))
        for f in ("n_matched_treated", "n_matched_control", "n_groups"):
            check(int(np.asarray(getattr(est, f))) == int(want[f]),
                  f"{t} {sub}: {f} {np.asarray(getattr(est, f))} != "
                  f"reference {want[f]}")
        for f in ("ate", "att"):
            got = float(np.asarray(getattr(est, f)))
            tol = tolerance(want[f"scale_{f}"], lengths[t])
            err = abs(got - want[f])
            check(np.isfinite(got) and err <= tol,
                  f"{t} {sub}: {f} {got!r} vs reference {want[f]!r} "
                  f"(|err| {err:.3g} > tol {tol:.3g})")
            worst = max(worst, err / tol if tol else 0.0)
            worst_err = max(worst_err, err)
    log(f"reference: {len(ests)} queries x (ATE, ATT) within tolerance; "
        f"largest |err| {worst_err!r}, largest |err|/tol {worst:.4f} "
        f"(tol = 2 u (ceil(log2 L) + 4) sum w(|m_t|+|m_c|)/sum w, "
        f"u = 2^-24, L = view capacity)")
    return worst


def check_exact_sums(ref: Dict[str, dict], log: Log) -> None:
    big = max(int(r["max_sum_yy"]) for r in ref.values())
    check(big < 1 << 24, f"a group's sum of y^2 is {big} >= 2^24: f32 group "
          "stats would not be exact")
    log(f"exact f32 group sums: largest group sum of y^2 {big:,} < 2^24")


def view_lengths(engine) -> Dict[str, int]:
    """Slots each view's query reduces over (padded to a power of two
    inside the reduction)."""
    out = {}
    for t, view in engine.views.items():
        out[t] = int(np.prod(view.table.key_hi.shape))
    return out


def check_groups(engine, ref: Dict[str, dict], log: Log) -> Dict[str, int]:
    st = engine.stats()
    live = {}
    for t, r in ref.items():
        live[t] = st[t]["n_groups"]
        check(st[t]["n_groups"] == r["n_groups_all"],
              f"view {t}: {st[t]['n_groups']} live groups, reference "
              f"{r['n_groups_all']}")
        check(st[t]["n_matched_groups"] == r["n_groups_matched"],
              f"view {t}: {st[t]['n_matched_groups']} matched groups, "
              f"reference {r['n_groups_matched']}")
    log("live groups per view: " + ", ".join(
        f"{t} {st[t]['n_groups']:,} ({st[t]['n_matched_groups']:,} matched, "
        f"capacity {st[t]['capacity']:,})" for t in sorted(ref))
        + f"; base {st['__base__']['n_groups']:,}")
    return live


def canonical_views(engine) -> Dict[str, Dict[str, np.ndarray]]:
    """Layout-free committed state: every view's key-sorted groups."""
    snap = engine.export_canonical()
    out = {}
    for name, v in snap["views"].items():
        flat = {"hi": v["hi"], "lo": v["lo"]}
        flat.update({f"stat.{k}": a for k, a in v["stats"].items()})
        if "keep" in v:
            flat["keep"] = v["keep"]
        out[name] = flat
    return out


def check_same_state(a: Dict, b: Dict, what: str) -> None:
    check(sorted(a) == sorted(b), f"{what}: different views")
    for name in a:
        for k in a[name]:
            x, y = np.asarray(a[name][k]), np.asarray(b[name][k])
            check(x.shape == y.shape and x.tobytes() == y.tobytes(),
                  f"{what}: view {name} column {k} differs")


def memory_line(devices=None) -> str:
    parts = []
    for d in devices or jax.devices():
        ms = d.memory_stats() or {}
        parts.append(f"{d.id}: in use {ms.get('bytes_in_use', 'n/a')}, "
                     f"peak {ms.get('peak_bytes_in_use', 'n/a')}")
    return "device memory (bytes) " + "; ".join(parts)


# ----------------------------------------------------- one-chip phase
def ask_singles(engine, qs) -> Dict:
    """Uncached ``ate()`` for every query; each must be one dispatch."""
    out = {}
    for t, sub in qs:
        with count_dispatches(label="query") as n:
            est = engine.ate(t, sub)
        check(n() == 1, f"uncached ate({t}, {sub}) took {n()} dispatches")
        out[(t, _key(sub))] = est
    return out


def _key(sub):
    return None if sub is None else tuple(
        (d, tuple(v)) for d, v in sorted(sub.items()))


def _sub(key):
    return None if key is None else {d: list(v) for d, v in key}


def ask_wave(engine, specs: List[QuerySpec]) -> List:
    """One ``ate_batch`` wave over an empty estimate cache: 1 dispatch."""
    engine._cache.clear()
    with count_dispatches(label="query") as n:
        out = engine.ate_batch(specs)
    check(n() == 1, f"ate_batch wave of {len(specs)} took {n()} "
          "dispatches")
    return out


def check_wave_matches_singles(engine, specs, wave) -> None:
    """Each wave slot bitwise equal to the sequential uncached ate()."""
    engine._cache.clear()
    for spec, got in zip(specs, wave):
        want = engine.ate(spec.treatment, _sub(spec.subpopulation))
        check(est_bits(got) == est_bits(want),
              f"ate_batch slot {spec} != sequential ate(): "
              f"{got} vs {want}")


def run_single(size: Size, seed: int, workdir: str, log: Log = print
               ) -> Dict:
    """The one-chip phase (module docstring, steps 1-4)."""
    t0 = time.perf_counter()
    stream = make_stream(size, seed)
    log(f"data: FLIGHTDELAY (paper §5 schema) from flightgen seed {seed}: "
        f"{stream.n_rows:,} flights x {stream.n_weather:,} hourly weather "
        f"rows ({size.n_airports} airports, {size.n_carriers} carriers, "
        f"{size.n_days} days), joined on the device: "
        f"{table_bytes(stream.table):,} B fact table, "
        f"{int(stream.host['_valid'].sum()):,} valid rows "
        f"[{time.perf_counter() - t0:.1f} s]; cut from the paper's full "
        "relation (every DOT flight joined to its hourly weather) in scale "
        "only: rows, airports, carriers and days; schema, treatments and "
        "coarsening as in the paper")
    ref = reference(stream, size)
    check_exact_sums(ref, log)

    wal_dir = os.path.join(workdir, "durable")
    live = DurableEngine(make_engine(OnlineEngine, size), wal_dir)
    sched = stream.schedule(size)
    t_first = _timed_ingest(live, stream, *sched[0])
    log(f"seed batch of {size.seed_rows:,} rows ingested in {t_first:.1f} s "
        "(compiles included)")
    times, dispatches = [], []
    last = _program_key(live.engine, sched[0])
    for i, (s, e) in enumerate(sched[1:], start=1):
        before = _program_key(live.engine, (s, e))
        with count_dispatches() as n:
            times.append(_timed_ingest(live, stream, s, e))
        after = _program_key(live.engine, (s, e))
        if before == after == last:
            # the program this batch ran was compiled by an earlier one
            check(n() == 1, f"steady batch {i} took {n()} dispatches")
            dispatches.append(n())
        last = after
        if i == size.ckpt_after:
            t_c = time.perf_counter()
            live.checkpoint(wait=True)
            log(f"checkpoint after batch {i} written in "
                f"{time.perf_counter() - t_c:.1f} s")
    check(live.engine.n_rows_ingested == size.n_flights,
          f"ingested {live.engine.n_rows_ingested} rows, want "
          f"{size.n_flights}")
    check(len(dispatches) >= size.n_batches // 2,
          f"only {len(dispatches)} of {size.n_batches} batches were steady")
    log(f"ingest: {live.engine.n_rows_ingested:,} rows = "
        f"{size.seed_rows:,} seed + {size.n_batches} x {size.batch_rows:,}"
        f"; {len(dispatches)} steady batches at 1 dispatch each, the other "
        f"{size.n_batches - len(dispatches)} compiled a program (new batch "
        f"shape, or a view grew to a doubled capacity); checkpoint after "
        f"batch {size.ckpt_after}")
    log(f"timing (one observation, not a benchmark number): seed batch "
        f"{t_first:.3f} s incl. compile; first streamed batch "
        f"{times[0]:.3f} s incl. compile; steady median "
        f"{float(np.median(times[1:])):.4f} s/batch")

    lengths = view_lengths(live.engine)
    live_groups = check_groups(live.engine, ref, log)
    sb = live.engine.state_bytes()
    log(f"state_bytes: {sb['total']:,} total, {sb['per_device']:,} per "
        f"device; {memory_line()}")

    qs = single_queries(size, seed)
    t_q = time.perf_counter()
    singles = ask_singles(live.engine, qs)
    t_q = time.perf_counter() - t_q
    worst = check_against_reference(singles, ref, lengths, log)
    specs = wave_specs(size, seed)
    t_w = time.perf_counter()
    wave = ask_wave(live.engine, specs)
    t_w = time.perf_counter() - t_w
    check_wave_matches_singles(live.engine, specs, wave)
    log(f"queries: {len(qs)} uncached ate() at 1 dispatch each "
        f"[{t_q:.2f} s incl. compile]; ate_batch wave of {len(specs)} "
        f"distinct specs in 1 dispatch [{t_w:.2f} s incl. compile], every "
        f"slot bitwise equal to the sequential ate()")

    state = canonical_views(live.engine)
    live.close()
    t_r = time.perf_counter()
    rec = DurableEngine.recover(make_engine(OnlineEngine, size), wal_dir)
    t_r = time.perf_counter() - t_r
    check(rec.engine.n_rows_ingested == live.engine.n_rows_ingested,
          "recovered engine row count differs")
    check_same_state(state, canonical_views(rec.engine),
                     "recovered vs live")
    rec.engine._cache.clear()
    again = ask_singles(rec.engine, qs)
    for k, est in singles.items():
        check(est_bits(again[k]) == est_bits(est),
              f"recovered ate{k} != live")
    rwave = ask_wave(rec.engine, specs)
    for spec, a, b in zip(specs, rwave, wave):
        check(est_bits(a) == est_bits(b), f"recovered wave slot {spec} "
              "!= live")
    rec.close()
    log(f"recovery: checkpoint + {size.n_batches - size.ckpt_after}-batch "
        f"WAL tail in {t_r:.2f} s (one observation, compiles included); "
        f"canonical state, {len(qs)} ate() and the wave bitwise equal to "
        f"the live engine")
    return dict(live_groups=live_groups, worst=worst,
                dispatches=dispatches, state_bytes=sb)


def _program_key(engine, span: Tuple[int, int]) -> Tuple:
    """What selects the compiled ingest program: view capacities, delta
    capacity and batch rows."""
    return engine._fused_caps(), engine._delta_cap, span[1] - span[0]


def _timed_ingest(dur: DurableEngine, stream: FlightStream, s: int,
                  e: int) -> float:
    batch = stream.batch(s, e)
    t0 = time.perf_counter()
    dur.ingest(batch)            # journals + fsyncs, then one dispatch
    dur.commit()
    return time.perf_counter() - t0


# ---------------------------------------------------- four-chip phase
def mesh_wave_specs(size: Size, seed: int) -> List[QuerySpec]:
    """The mesh phase's one wave: every single query of the one-chip
    phase (overall and per airport, per treatment) first, then distinct
    airport-subset specs up to ``size.mesh_wave`` slots."""
    out = [QuerySpec.make(t, sub) for t, sub in single_queries(size, seed)]
    seen = {(s.treatment, s.subpopulation) for s in out}
    for spec in wave_specs(size, seed):
        if len(out) == size.mesh_wave:
            break
        if (spec.treatment, spec.subpopulation) not in seen:
            seen.add((spec.treatment, spec.subpopulation))
            out.append(spec)
    return out


def run_mesh(size: Size, seed: int, n_devices: int = 4, log: Log = print
             ) -> Dict:
    """Partitioned views and row-sharded ingest over an ``n_devices``
    mesh against a one-device engine, in this one process.

    The three engines ingest the same equal batches and answer the same
    wave, each on its own thread: their programs compile concurrently
    (the TPU compiler spends tens of seconds on every program that sorts
    2^16 or more slots) while device work interleaves, and this thread
    computes the reference meanwhile. Each engine's own calls stay in
    order, so its state is what a lone run would build."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.launch.mesh import make_data_mesh
    check(len(jax.devices()) >= n_devices,
          f"{n_devices} devices needed, JAX sees {len(jax.devices())}")
    t0 = time.perf_counter()
    stream = make_stream(size, seed)
    log(f"data: {stream.n_rows:,} flights x {stream.n_weather:,} weather "
        f"rows joined on the device, seed {seed} "
        f"[{time.perf_counter() - t0:.1f} s]")
    mesh = make_data_mesh(n_devices)
    sizing = dict(granule=size.mesh_granule,
                  delta_granule=size.mesh_delta_granule)
    engines = {
        "partitioned": make_engine(PartitionedOnlineEngine, size,
                                   n_parts=2 * n_devices, mesh=mesh,
                                   **sizing),
        "row-sharded": make_engine(OnlineEngine, size, mesh=mesh, **sizing),
        "one-device": make_engine(OnlineEngine, size, **sizing),
    }
    spans = stream.equal_batches(size.mesh_batches)
    specs = mesh_wave_specs(size, seed)

    def drive(name, eng):
        t = time.perf_counter()
        for s, e in spans:
            eng.ingest(stream.batch(s, e))
        t_ingest = time.perf_counter() - t
        log(f"{name}: {stream.n_rows:,} rows ingested")
        t = time.perf_counter()
        wave = ask_wave(eng, specs)
        log(f"{name}: wave of {len(specs)} answered")
        return wave, t_ingest, time.perf_counter() - t

    with ThreadPoolExecutor(len(engines)) as pool:
        futures = {name: pool.submit(drive, name, eng)
                   for name, eng in engines.items()}
        ref = reference(stream, size)
        runs = {name: f.result() for name, f in futures.items()}
    check_exact_sums(ref, log)
    log(f"ingest: {stream.n_rows:,} rows in {size.mesh_batches} batches of "
        f"{spans[0][1] - spans[0][0]:,} into each engine (view capacity "
        f"{size.mesh_granule:,}, delta capacity {size.mesh_delta_granule:,}"
        "); seconds incl. compile, one observation: " + ", ".join(
            f"{name} ingest {r[1]:.1f} wave {r[2]:.1f}"
            for name, r in runs.items()))
    part = engines["partitioned"]
    one = engines["one-device"]
    states = {name: canonical_views(eng) for name, eng in engines.items()}
    for name in ("partitioned", "row-sharded"):
        check_same_state(states["one-device"], states[name],
                         f"{name} vs one-device")
    check_groups(one, ref, log)
    waves = {name: r[0] for name, r in runs.items()}
    for name in ("partitioned", "row-sharded"):
        for spec, a, b in zip(specs, waves[name], waves["one-device"]):
            check(est_bits(a) == est_bits(b),
                  f"{name} wave slot {spec} != one-device")
    worst = check_against_reference(
        {(s.treatment, s.subpopulation): est
         for s, est in zip(specs, waves["one-device"])},
        ref, view_lengths(one), log)
    log(f"bit-identity: canonical state and a {len(specs)}-spec ate_batch "
        f"wave (1 dispatch each) on the {n_devices}-device mesh identical "
        "across partitioned, row-sharded and one-device engines")
    per_dev = partition_bytes(part, mesh.devices.reshape(-1))
    total = sum(per_dev.values())
    share = {d: b / total for d, b in per_dev.items()}
    check(all(abs(x - 1 / n_devices) < 0.05 / n_devices
              for x in share.values()),
          f"partitioned state per device {per_dev} is not ~1/{n_devices}")
    log("partitioned state bytes per device: " + ", ".join(
        f"{d} {b:,} ({share[d]:.3f})" for d, b in per_dev.items())
        + f"; total {total:,}")
    log(memory_line(list(mesh.devices.reshape(-1))))
    return dict(worst=worst, per_device=per_dev)


def partition_bytes(engine, devices) -> Dict[int, int]:
    """Bytes of ``engine``'s materialized state held by each device."""
    out = {d.id: 0 for d in devices}
    for a in engine._state_arrays():
        for sh in a.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + int(
                sh.data.nbytes)
    return out
