"""Online incremental causal inference (paper §4.2's "online setting",
made truly incremental — and sharded over the device mesh).

The offline path re-coarsens, re-groups and re-cubes the whole relation for
every new batch of rows. This engine instead maintains causal estimates
under streaming INSERTs with work proportional to the DELTA, not the data:

  1. DELTA CUBOID MAINTENANCE — every cuboid stat is decomposable
     (count/sum), so a streamed batch reduces to a tiny stat table that is
     folded into each materialized cuboid with the same combine the
     distributed engine uses for per-chip partials
     (:func:`repro.core.cube.merge_delta`). The delta is computed ONCE at
     base granularity and propagated DOWN the cube lattice by rolling the
     delta itself up to each view's dims — never by rebuilding a cuboid
     from rows.
  2. SHARDED INGEST — on a multi-device mesh the batch is row-sharded over
     the data axis: each device coarsens/packs/locally-aggregates its
     shard, the per-device delta stat tables are ``all_gather``ed and
     combined (:func:`repro.core.distributed.make_sharded_delta_build`),
     and the replicated merged delta folds into every view exactly as on
     one chip — the offline-equivalence guarantees carry over verbatim on
     1..N devices. :class:`PartitionedOnlineEngine` goes further: the
     MATERIALIZED views themselves are key-range partitioned over the mesh
     (each device owns 1/N of every stat table), deltas are ROUTED to
     their owner device (all-to-all on key range instead of
     all-gather-everything), and merges/eviction run per partition — total
     state scales with the mesh instead of being capped by one chip.
  3. INCREMENTAL CEM OVERLAP — when a merge keeps the stat-table layout
     (fast path), the overlap filter ``max(T) != min(T)`` is re-evaluated
     only at the group ids the delta touched
     (:func:`repro.core.cem.update_overlap`): groups flip in and out of the
     matched set in O(|delta groups|).
  4. STREAMING PROPENSITY — logistic refreshes no longer need an unbounded
     row log: a :class:`repro.core.propensity.StreamStats` maintains exact
     per-feature moment accumulators (stream-wide standardization,
     retractable) plus a bounded uniform reservoir that the warm-started
     Newton refit (:func:`repro.core.propensity.warm_refit`) runs over.
  5. ESTIMATE CACHE — repeated online queries are served from a cache keyed
     by (treatment, sub-population); a delta invalidates only the entries
     whose group predicate it actually touched.
  6. ONE FUSED HOST SYNC PER INGEST — the per-merge fast/slow-path
     decisions, the retraction guard, the delta group count, and the cache
     invalidation predicate all come back from the device in a single
     ``device_get`` (:func:`_plan_ingest`), instead of one blocking
     device->host read per merge serializing dispatch every batch.
  7. ONE COMPILED DISPATCH PER INGEST (``pipeline="fused1"``, the default)
     — the whole maintenance loop of a batch (delta build, rollups,
     routing, merges incl. the re-sort grow path, overlap flips, touch
     stamps, streaming-propensity update, verdict scalars) is one donated
     device program (:mod:`repro.core.fused`): state updates in place, the
     host fetches one verdict ``device_get`` and commits by reference
     swap. Growth (a view or the delta table outgrowing its capacity)
     recompiles the program at a doubled capacity (capacities step along
     ``granule * 2**k``) and re-dispatches. ``pipeline="planner"``
     keeps the PR 3 two-dispatch planner path and ``pipeline="unfused"``
     the legacy per-merge-sync loop, both measurable in
     ``benchmarks/bench_online.py``.
  8. ONE COMPILED DISPATCH PER QUERY (``query_pipeline="fused"``, the
     default) — an uncached ``ate()`` runs subpopulation filtering, keep
     masking and the sufficient-stat reductions inside one device program
     straight on the raw materialized state (per-partition/1-per-device
     on a mesh), fetches one scalar dict, and caches it host-side
     (delta-predicate invalidation, item 5): repeated dashboard queries
     are zero dispatches and zero transfers, and the partitioned
     engine's canonical-reassembly memo is keyed on a state version
     bumped per commit. ``matched_rows`` is a one-dispatch
     routed row lookup on the partitioned layout. The canonical chunked
     reduction (:func:`repro.kernels.segment_stats.canonical_sum`) makes
     every estimate a bitwise-deterministic function of the group content
     alone, so the fused path, the ``query_pipeline="assemble"``
     baseline and both engine layouts agree exactly.
  9. MVCC SNAPSHOT OVERLAP (``overlap=True``) — ingest dispatches for
     version v+1..v+k run PIPELINED while every query path serves the
     last COMMITTED snapshot v: the engine's attributes only ever hold
     committed state, in-flight dispatches chain device-side off each
     other (the first hop does NOT donate the committed buffers — the
     MVCC double-buffer rule, :func:`repro.core.fused.get_fused_ingest`),
     and the verdict scalars are checked LAZILY at :meth:`commit` — the
     steady-state ingest hot path performs ZERO host syncs
     (``device_get`` leaves the dispatch path entirely; rule ZQL007 and
     the jaxpr audit enforce it). Commit is an atomic reference swap plus
     one version bump per batch; a batch that needed growth or the exact
     fallback rolls BACK to the committed snapshot and REPLAYS all
     in-flight batches synchronously in order, so every committed version
     is bitwise identical to the synchronous pipeline's. Every
     ``ATEEstimate`` carries ``state_version`` — the snapshot it was
     computed at (:meth:`snapshot_version`).

The maintained state is EXACT: after any number of ingested batches, every
cuboid stat, CEM matched set and ATE equals the offline computation over
the concatenated table (bit-identical when outcome sums are exact, e.g.
integer-valued outcomes; to float tolerance otherwise — summation order is
the only difference). ``tests/test_online.py`` asserts this equivalence,
and ``tests/test_online_sharded.py`` asserts it per device count. Eviction
(:meth:`OnlineEngine.evict`) deliberately trades this exactness for
bounded state on unbounded key spaces.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cube as cube_mod
from repro.core import fused as fused_mod
from repro.core import groupby
from repro.core.ate import ATEEstimate
from repro.core.cem import (CEMGroups, make_codec, overlap_keep, pack_keys,
                            update_overlap)
from repro.core.coarsen import CoarsenSpec
from repro.core.propensity import (LogisticModel, StreamStats, design_matrix,
                                   fit_logistic)
from repro.data.columnar import GrowableTable, Table
from repro.launch.trace import (count, counted_jit, device_fetch,
                                record_batch, span)

import collections.abc as _cabc

#: contract-lint scoping (tools/contract_check.py): this module is
#: engine-owned — dispatch/donation rules ZQL001-ZQL006 apply.
__engine_owned__ = True

BASE_VIEW = fused_mod.BASE_VIEW

# The query reductions run through repro.kernels.segment_stats.
# canonical_sum: the key-sorted group stats reduce by a pairwise fold
# whose association is fixed in the program, so estimates are a function
# of the canonical group CONTENT alone — never of an engine's capacity,
# growth history or partition count — and the same state yields
# bit-identical results from every engine layout and query pipeline on
# any device count.

# Streamed batches are padded to power-of-two row buckets (floor below)
# before they reach the compiled ingest pipeline: the fused program traces
# per row-count, so bucketing caps the trace count of an irregular stream
# at ~log2(max batch) instead of one trace per distinct size. Padding rows
# are invalid (masked everywhere, including the streaming-propensity
# update, which sees the same padded draw shape in every engine — that is
# what keeps reservoir states bit-identical across engines and pipelines).
BATCH_BUCKET_GRANULE = 64


def _bucket_rows(n: int) -> int:
    """Power-of-two row bucket (>= BATCH_BUCKET_GRANULE) a batch pads to."""
    b = BATCH_BUCKET_GRANULE
    while b < n:
        b <<= 1
    return b


def _capacity_ladder(n: int, granule: int) -> int:
    """Smallest ``granule * 2**k`` holding ``n`` groups: the one rule by
    which view and delta capacities grow, shrink and are restored. The
    compiled ingest and query programs are keyed on capacities, so two
    engines that reach the same group counts by different histories
    (a live engine and one recovered from its checkpoint) land on the
    same capacities and share programs; on the TPU compiler each program
    that sorts 2^16 or more slots takes tens of seconds to build."""
    cap = granule
    while cap < n:
        cap <<= 1
    return cap


def _bucket_specs(n: int) -> int:
    """Power-of-two SPEC bucket a query batch pads to. Same idea as
    :func:`_bucket_rows` (the batched query program traces per padded
    batch size, so bucketing caps retraces at ~log2(max B)) but floored
    at 1: single queries through the batched path should not pay a
    64-wide estimate."""
    b = 1
    while b < n:
        b <<= 1
    return b


SubPop = Optional[Mapping[str, Sequence[int]]]


class PoisonBatchError(ValueError):
    """A streamed batch failed host-side validation BEFORE any dispatch,
    WAL append or state mutation: the engine's committed state, snapshot
    version, estimate cache and in-flight MVCC chain are all untouched
    (exception safety asserted by ``tests/test_online_recovery.py``)."""


def _freeze_subpop(subpopulation: SubPop):
    """Canonical hashable form of a subpopulation predicate: ``((dim,
    (bucket, ...)), ...)`` sorted, or None. Idempotent — accepts either
    the mapping form or an already-frozen tuple (``QuerySpec`` stores the
    frozen form)."""
    if not subpopulation:
        return None
    items = (subpopulation if isinstance(subpopulation, tuple)
             else subpopulation.items())
    return tuple(sorted((d, tuple(sorted(int(b) for b in bs)))
                        for d, bs in items))


@dataclasses.dataclass
class DeltaReport:
    """What one :meth:`OnlineEngine.ingest` call did."""

    n_rows: int                   # batch rows (valid or not)
    n_delta_groups: int           # distinct base-granularity groups touched
    fast_path: Dict[str, bool]    # view -> scatter-merge (True) / re-sort
    invalidated: Tuple            # estimate-cache keys dropped


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-uncommitted MVCC ingest hop: the program's
    output state pytree (the NEXT hop's input), its device-resident
    verdict scalars (fetched lazily at commit), the bucket-padded batch
    (the replay input on rollback) and the caller's original batch (row
    accounting)."""

    state: dict
    verdicts: dict
    batch: "Table"
    orig: "Table"
    pending: "PendingIngest"


class PendingIngest:
    """Lazy :class:`DeltaReport` of one overlap-mode ingest.

    The dispatch already happened; the verdict scalars stay on device
    until :meth:`OnlineEngine.commit` fetches them all in ONE
    ``device_get``. ``n_rows`` is known immediately; touching any
    verdict-derived field (``n_delta_groups``, ``fast_path``,
    ``invalidated``) forces the commit — so code written against the
    synchronous ``DeltaReport`` keeps working, it just pays the sync it
    asks for."""

    def __init__(self, engine: "OnlineEngine", n_rows: int):
        self._engine = engine
        self.n_rows = n_rows
        self.report: Optional[DeltaReport] = None

    @property
    def committed(self) -> bool:
        return self.report is not None

    def _force(self) -> DeltaReport:
        if self.report is None:
            self._engine.commit()
        return self.report

    @property
    def n_delta_groups(self) -> int:
        return self._force().n_delta_groups

    @property
    def fast_path(self) -> Dict[str, bool]:
        return self._force().fast_path

    @property
    def invalidated(self) -> Tuple:
        return self._force().invalidated


class EvictReport(_cabc.Mapping):
    """Lazy ``{view: groups evicted}`` mapping returned by
    :meth:`OnlineEngine.evict`.

    The eviction program's count/occupancy scalars stay on device (their
    host copy is started async) so ``evict()`` never blocks the python
    thread behind an in-flight ingest dispatch; the engine resolves them
    — ONE ``device_get``, then the scoped cache invalidation and the
    capacity-shrink pass — at its next sync point
    (:meth:`OnlineEngine._resolve_evictions`) or on first access here,
    whichever comes first. Compares equal to the plain dict it resolves
    to."""

    def __init__(self, engine: "OnlineEngine"):
        self._engine = engine
        self._counts: Optional[Dict[str, int]] = None

    def _resolve(self) -> Dict[str, int]:
        if self._counts is None:
            self._engine._resolve_evictions()
        return self._counts

    def __getitem__(self, key: str) -> int:
        return self._resolve()[key]

    def __iter__(self):
        return iter(self._resolve())

    def __len__(self) -> int:
        return len(self._resolve())

    def __eq__(self, other):
        if isinstance(other, EvictReport):
            other = dict(other._resolve())
        if not isinstance(other, dict):
            return NotImplemented
        return dict(self._resolve()) == other

    def __repr__(self) -> str:
        if self._counts is None:
            return "EvictReport(<unresolved>)"
        return f"EvictReport({self._counts!r})"


@dataclasses.dataclass
class _View:
    """One materialized cuboid + incrementally maintained overlap mask."""

    treatment: str
    dims: Tuple[str, ...]
    cuboid: cube_mod.Cuboid
    keep: jnp.ndarray

    @property
    def table(self):
        """Uniform accessor over replicated/partitioned view state."""
        return self.cuboid

    def set_table(self, tab) -> None:
        self.cuboid = tab


@dataclasses.dataclass
class _PartView:
    """One key-range partitioned cuboid + per-partition overlap mask."""

    treatment: str
    dims: Tuple[str, ...]
    pcub: cube_mod.PartitionedCuboid
    keep: jnp.ndarray            # (P, C)

    @property
    def table(self):
        return self.pcub

    def set_table(self, tab) -> None:
        self.pcub = tab


def _estimate_view(cub: cube_mod.Cuboid, keep: jnp.ndarray, treatment: str,
                   subpopulation: SubPop) -> ATEEstimate:
    """Causal estimate over one materialized view's stat table — ONE
    compiled dispatch, no host round trip anywhere on the path.

    The subpopulation filter, the keep mask and the estimate reductions
    all run inside the same device program
    (:func:`repro.core.fused.estimate_view_body`): the surviving groups
    are re-sorted into canonical key order in-program and reduced with the
    capacity-invariant canonical sum, so the float reductions are
    deterministic functions of the maintained group stats alone —
    replicated and partitioned engines (any partition count, any
    capacity-growth history) return bit-identical ATE, ATT and Neyman
    variance for identical state. The former host-side
    ``compact_cuboid`` + blocking ``np.asarray(keep)`` transfer are gone
    from the query path entirely; this shared body is also the
    ``query_pipeline="assemble"`` baseline and the differential oracle's
    estimator."""
    prog = fused_mod.get_fused_query(cub.codec, treatment,
                                     _freeze_subpop(subpopulation))
    stats = {k: cub.stats[k] for k in fused_mod.query_stat_names(treatment)}
    return ATEEstimate(**prog(cub.key_hi, cub.key_lo, stats,
                              cub.group_valid, keep))


# Touch-stamp helpers: the pure bodies live in ``repro.core.fused`` (the
# single-dispatch program traces them inline); these counted-jit wrappers
# are the standalone dispatches the planner/unfused paths still issue, so
# the dispatch counter (repro.launch.trace) accounts for them.
_stamp_touch = counted_jit(fused_mod.stamp_touch)
_remap_touch_arrays = counted_jit(fused_mod.remap_touch)
_stamp_touch_parts = counted_jit(
    jax.vmap(fused_mod.stamp_touch, in_axes=(0, 0, 0, None)))
_remap_touch_parts_arrays = counted_jit(jax.vmap(fused_mod.remap_touch))


def _remap_touch(old_cub: cube_mod.Cuboid, new_cub: cube_mod.Cuboid,
                 touch: jnp.ndarray) -> jnp.ndarray:
    """Carry last-touch stamps across a layout-changing (re-sort) merge."""
    return _remap_touch_arrays(old_cub.key_hi, old_cub.key_lo,
                               old_cub.group_valid, new_cub.key_hi,
                               new_cub.key_lo, touch)


def _remap_touch_parts(old: cube_mod.PartitionedCuboid,
                       new: cube_mod.PartitionedCuboid,
                       touch: jnp.ndarray) -> jnp.ndarray:
    """Carry (P, C) last-touch stamps across a per-partition re-sort merge
    or compaction. Keys never change partition (the owner is a pure
    function of the key), so the remap is partition-local."""
    return _remap_touch_parts_arrays(old.key_hi, old.key_lo, old.group_valid,
                                     new.key_hi, new.key_lo, touch)


@functools.partial(
    counted_jit,
    static_argnames=("codec", "tnames", "vdims", "retract", "use_pallas",
                     "dcap"))
def _plan_ingest(d_hi, d_lo, d_stats, d_gv, base_hi, base_lo, base_stats,
                 view_hi, view_lo, view_stats, view_gv, view_keep, *,
                 codec, tnames, vdims, retract, use_pallas, dcap):
    """Everything one ingest must know, computed in ONE device program.

    Produces, without any host round-trip: the per-view rolled-up deltas,
    the fast/slow-path verdicts (is every delta key already materialized?),
    the fast-path merge candidates with their updated overlap masks, the
    retraction-negativity probe, and the cache-invalidation predicate
    inputs. The engine then issues a single fused ``device_get`` for the
    scalars/small vectors it needs to branch on — replacing the one-sync-
    per-merge pattern that serialized device dispatch on every batch.
    """
    d_hi, d_lo, d_gv = d_hi[:dcap], d_lo[:dcap], d_gv[:dcap]
    d_stats = {k: v[:dcap] for k, v in d_stats.items()}
    if retract:
        d_stats = {k: -v for k, v in d_stats.items()}
    pos_b, found_b = groupby.lookup_rows_in_table(d_hi, d_lo,
                                                  base_hi, base_lo)
    ok_b = jnp.all(found_b | ~d_gv)
    merged_b = cube_mod.scatter_merge_stats(base_stats, pos_b, d_stats,
                                            use_pallas=use_pallas)
    count_cols = [merged_b["one"]] + [merged_b[f"t_{t}"] for t in tnames]
    neg_min = jnp.min(jnp.stack(count_cols))
    views = {}
    for t, dims in zip(tnames, vdims):
        roll = cube_mod._rollup_fn(codec, dims)
        v_hi, v_lo, v_stats, v_gv = roll(d_hi, d_lo, d_gv, d_stats)
        pos_v, found_v = groupby.lookup_rows_in_table(
            v_hi, v_lo, view_hi[t], view_lo[t])
        ok_v = jnp.all(found_v | ~v_gv)
        m_stats = cube_mod.scatter_merge_stats(view_stats[t], pos_v, v_stats,
                                               use_pallas=use_pallas)
        nt = m_stats[f"t_{t}"]
        nc = m_stats["one"] - nt
        new_keep = update_overlap(view_keep[t], view_gv[t], nt, nc, pos_v)
        views[t] = dict(delta=(v_hi, v_lo, v_stats, v_gv), pos=pos_v,
                        ok=ok_v, stats=m_stats, keep=new_keep)
    buckets = {d: codec.extract(d_hi, d_lo, d) for d in codec.names}
    return dict(d_stats=d_stats, d_keys=(d_hi, d_lo), pos_b=pos_b,
                ok_b=ok_b, merged_b=merged_b,
                neg_min=neg_min, views=views, buckets=buckets,
                gv=d_gv, n_delta=jnp.sum(d_gv.astype(jnp.int32)))


@functools.partial(
    counted_jit, static_argnames=("codec", "tnames", "retract", "use_pallas"))
def _plan_ingest_parts(deltas, base_hi, base_lo, base_stats, view_hi,
                       view_lo, view_stats, view_gv, view_keep, *,
                       codec, tnames, retract, use_pallas):
    """Partitioned analogue of :func:`_plan_ingest`: every per-view,
    per-partition decision of one ingest in ONE device program.

    ``deltas`` holds the ROUTED delta stat tables — (P, Cd) per view, each
    partition's rows already delivered to its owner — so lookups, scatter
    merges and overlap re-evaluation are partition-local vmaps with no
    cross-partition traffic; on a mesh the leading axis is sharded and the
    whole plan runs 1/N-per-device. The engine fetches one fused
    ``device_get`` of the verdict scalars, exactly like the replicated
    fused path."""
    out_pos, out_ok, out_merged, out_keep = {}, {}, {}, {}
    neg_min = jnp.float32(0.0)
    n_delta = jnp.int32(0)
    buckets = {}
    for name in (BASE_VIEW,) + tnames:
        d_hi, d_lo, d_stats, d_gv = deltas[name]
        if retract:
            d_stats = {k: -v for k, v in d_stats.items()}
        if name == BASE_VIEW:
            t_hi, t_lo, t_stats = base_hi, base_lo, base_stats
        else:
            t_hi, t_lo, t_stats = view_hi[name], view_lo[name], \
                view_stats[name]
        pos, found = jax.vmap(groupby.lookup_rows_in_table)(
            d_hi, d_lo, t_hi, t_lo)
        out_ok[name] = jnp.all(found | ~d_gv)
        merged = cube_mod.scatter_merge_stats_parts(
            t_stats, pos, d_stats, use_pallas=use_pallas)
        out_pos[name], out_merged[name] = pos, merged
        if name == BASE_VIEW:
            count_cols = [merged["one"]] + [merged[f"t_{t}"]
                                            for t in tnames]
            neg_min = jnp.min(jnp.stack(count_cols))
            n_delta = jnp.sum(d_gv.astype(jnp.int32))
            buckets = {d: codec.extract(d_hi, d_lo, d)
                       for d in codec.names}
        else:
            nt = merged[f"t_{name}"]
            nc = merged["one"] - nt
            out_keep[name] = jax.vmap(update_overlap)(
                view_keep[name], view_gv[name], nt, nc, pos)
    return dict(pos=out_pos, ok=out_ok, merged=out_merged, keep=out_keep,
                neg_min=neg_min, buckets=buckets, n_delta=n_delta)


class OnlineEngine:
    """Streaming causal-inference engine over a fixed coarsening schema.

    specs:       covariate -> CoarsenSpec (the coarsening is part of the
                 schema: delta maintenance needs stable group keys).
    treatments:  treatment name -> its covariate names (the CDAG choice).
    query_dims:  extra dims kept in every view so sub-population queries
                 (e.g. airport=SFO) stay answerable from materialized state.
    keep_rows:   also log raw rows (append-only, geometric growth) — needed
                 only for row-level diagnostics; propensity refreshes now
                 run off the bounded streaming reservoir instead.
    reservoir_size: rows of streaming-propensity reservoir state kept per
                 engine. Default-on so ``refresh_propensity`` works out of
                 the box without a row log; it costs one jitted top-k
                 merge per ingest (no host sync) — pass 0 to disable if
                 propensity refreshes are never needed.
    mesh:        a jax Mesh with a ``mesh_axis`` data axis: streamed batches
                 are row-sharded across it and per-device delta stat tables
                 combined via all-gather. None = single-device build.
    use_pallas:  route fast-path merges through the MXU scatter kernel.
    pipeline:    "fused1" (default) runs the WHOLE ingest as one donated
                 compiled dispatch (delta build + merges + overlap + touch
                 + reservoir in one program, state updated in place — see
                 :mod:`repro.core.fused`); "planner" keeps the two-dispatch
                 on-device planner; "unfused" the legacy
                 one-blocking-read-per-merge loop. All three maintain
                 bit-identical state; the non-default modes exist as
                 measurable baselines (``benchmarks/bench_online.py``).
    query_pipeline: "fused" (default) answers ``ate()`` /
                 ``matched_rows()`` with ONE compiled dispatch straight on
                 the raw materialized state (filter + keep + canonical
                 reduce in-program; routed row lookup on partitioned
                 views); "assemble" keeps the planner-era baseline that
                 first reassembles the canonical view. Both return
                 bit-identical results (the shared canonical estimator);
                 "assemble" exists as the measurable baseline.
    fused_host_sync: legacy alias — ``False`` selects
                 ``pipeline="unfused"``; ignored when ``pipeline`` is
                 passed explicitly.

    Which pipeline am I on?  (full table: docs/architecture.md)

    ==================  ===================  =========================
    flag                value                dispatches / role
    ==================  ===================  =========================
    ``pipeline=``       ``"fused1"``         1 donated (production)
    (ingest)            ``"planner"``        2 (PR 3 baseline)
                        ``"unfused"``        O(#views) (legacy)
    ``query_pipeline=`` ``"fused"``          1, 0 cached (production)
                        ``"assemble"``       reassembly baseline
    (no flag)           :meth:`ate_batch`    1 per B-spec wave
    ==================  ===================  =========================

    Many heterogeneous queries batch into ONE dispatch via
    :meth:`ate_batch` (specs are encoded as device-resident data, so
    changing WHAT a batch asks never retraces);
    :class:`repro.core.serving.ServingEngine` wraps it in a slot-based
    continuous batcher for the multi-tenant serving regime. Both share
    ``ate()``'s estimate cache and invalidation.
    """

    def __init__(self, specs: Mapping[str, CoarsenSpec],
                 treatments: Mapping[str, Sequence[str]], outcome: str,
                 query_dims: Sequence[str] = (), granule: int = 1024,
                 delta_granule: int = 256, keep_rows: bool = False,
                 row_granule: int = 4096, use_pallas: bool = False,
                 reservoir_size: int = 8192, mesh=None,
                 mesh_axis: str = "data", seed: int = 0,
                 fused_host_sync: bool = True, pipeline: str = None,
                 query_pipeline: str = "fused", overlap: bool = False,
                 max_inflight: int = 8):
        if pipeline is None:
            pipeline = "fused1" if fused_host_sync else "unfused"
        if pipeline not in ("fused1", "planner", "unfused"):
            raise ValueError(f"unknown pipeline {pipeline!r}")
        if query_pipeline not in ("fused", "assemble"):
            raise ValueError(f"unknown query_pipeline {query_pipeline!r}")
        if overlap and pipeline != "fused1":
            raise ValueError("overlap=True requires pipeline='fused1' "
                             "(the MVCC chain is a fused-dispatch protocol)")
        self.pipeline = pipeline
        self.query_pipeline = query_pipeline
        self.overlap = bool(overlap)
        self.max_inflight = int(max_inflight)
        self._inflight: List[_InFlight] = []
        self._pending_evict: Optional[Tuple] = None
        self._state_version = 0
        self.fused_host_sync = pipeline != "unfused"
        self.seed = seed
        self.treatments = {t: tuple(sorted(c)) for t, c in treatments.items()}
        self.outcome = outcome
        self.query_dims = tuple(query_dims)
        base_dims = sorted(set(self.query_dims).union(
            *[set(c) for c in self.treatments.values()]))
        missing = [d for d in base_dims if d not in specs]
        if missing:
            raise ValueError(f"no CoarsenSpec for dims {missing}")
        self.specs = {d: specs[d] for d in base_dims}
        self.codec = make_codec(self.specs)
        self.granule = granule
        self.delta_granule = delta_granule
        self.use_pallas = use_pallas
        self.row_granule = row_granule
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self._mesh_ndev = 1 if mesh is None else int(mesh.shape[mesh_axis])
        self._delta_cap = delta_granule
        self._sharded_builds: Dict[int, Callable] = {}
        tnames = sorted(self.treatments)
        self._row_cols = (*base_dims, *tnames, outcome)
        self._init_state()
        self._ingest_count = 0
        self.rows: Optional[GrowableTable] = (
            None if not keep_rows else GrowableTable.from_table(
                Table.from_numpy(
                    {c: np.zeros((0,), np.float32) for c in self._row_cols},
                    np.zeros((0,), bool)),
                granule=row_granule))
        self.stream: Optional[StreamStats] = (
            StreamStats.empty(self._row_cols, capacity=reservoir_size,
                              seed=seed) if reservoir_size > 0 else None)
        self.n_rows_ingested = 0
        self._cache: Dict[Tuple, ATEEstimate] = {}
        self.cache_hits = 0
        self.cache_misses = 0
        self.batch_deduped = 0
        self.models: Dict[str, LogisticModel] = {}

    def _view_schema(self):
        """(treatment, dims, codec) of every materialized view — shared by
        the replicated and partitioned state layouts."""
        for t in sorted(self.treatments):
            dims = tuple(sorted(set(self.treatments[t])
                                | set(self.query_dims)))
            yield t, dims, make_codec({d: self.specs[d] for d in dims})

    def _init_state(self) -> None:
        """Allocate the empty materialized views (replicated layout);
        :class:`PartitionedOnlineEngine` overrides this with per-partition
        tables, so no replicated state is ever allocated there."""
        tnames = tuple(sorted(self.treatments))
        self.base = cube_mod.empty_cuboid(self.codec, tnames,
                                          capacity=self.granule)
        self.views: Dict[str, _View] = {}
        for t, dims, vcodec in self._view_schema():
            self.views[t] = _View(
                treatment=t, dims=dims,
                cuboid=cube_mod.empty_cuboid(vcodec, tnames,
                                             capacity=self.granule),
                keep=jnp.zeros((self.granule,), bool))
        self._touch: Dict[str, jnp.ndarray] = {
            name: jnp.zeros((self.granule,), jnp.int32)
            for name in (BASE_VIEW, *tnames)}

    @classmethod
    def from_table(cls, table: Table, specs: Mapping[str, CoarsenSpec],
                   treatments: Mapping[str, Sequence[str]], outcome: str,
                   **kwargs) -> "OnlineEngine":
        """Seed the engine with an initial offline table, then stream."""
        eng = cls(specs, treatments, outcome, **kwargs)
        eng.ingest(table)
        return eng

    # ------------------------------------------------------- delta build
    def _get_sharded_build(self, capacity: int) -> Callable:
        if capacity not in self._sharded_builds:
            from repro.core.distributed import make_sharded_delta_build
            self._sharded_builds[capacity] = make_sharded_delta_build(
                self.mesh, self.specs, sorted(self.treatments),
                self.outcome, capacity, axis=self.mesh_axis)
        return self._sharded_builds[capacity]

    def _build_delta(self, batch: Table):
        """Raw (uncompacted) delta stat table of one batch, sharded over
        the mesh when one is attached. Returns device arrays only —
        (hi, lo, stats, group_valid, n_groups, overflow) — where overflow
        means the table is INCOMPLETE (a local shard overflowed its
        capacity) and the caller must rebuild exactly on the host.
        """
        cols = {c: batch.columns[c] for c in self._row_cols}
        valid = batch.valid
        if self.mesh is not None and self._mesh_ndev > 1:
            cols, valid = fused_mod.pad_tail(
                cols, valid, (-batch.nrows) % self._mesh_ndev)
            fn = self._get_sharded_build(self._delta_cap)
            return fn(cols, valid)
        fn = cube_mod._build_fn(self.codec,
                                tuple(sorted(self.specs.items())),
                                tuple(sorted(self.treatments)), self.outcome)
        hi, lo, stats, gv = fn(cols, valid)
        n_full = jnp.sum(gv.astype(jnp.int32))
        return hi, lo, stats, gv, n_full, jnp.asarray(False)

    # ------------------------------------------------------------- ingest
    def ingest(self, batch: Table, retract: bool = False) -> DeltaReport:
        """Fold one streamed batch into every materialized view.

        Work is O(batch/device + |delta groups| * #views) on the fast path;
        a full re-sort of a view's (tiny) stat table only happens when the
        delta introduces group keys that view has never seen.

        ``retract=True`` REMOVES previously ingested rows: every maintained
        stat is a count/sum, so retraction is exact sign-flipped delta
        maintenance — groups can lose overlap and flip back out of the
        matched set. Retracting rows that were never ingested would drive
        group counts negative and silently corrupt overlap masks, so it is
        detected (new keys, or any post-merge count below zero) and raises
        ``ValueError`` BEFORE any state is committed.

        The batch is padded to a power-of-two row bucket before it reaches
        any compiled pipeline (invalid padding rows contribute nothing),
        capping the fused program's retrace count for irregular streams at
        ~log2(max batch). Row accounting (``DeltaReport.n_rows``,
        ``n_rows_ingested``, the optional row log) stays on the original
        batch.

        With ``overlap=True`` (MVCC) this call only DISPATCHES: the fused
        program chains off the previous in-flight state while every query
        keeps serving the committed snapshot, the returned report is a
        lazy :class:`PendingIngest`, and the verdicts are checked at
        :meth:`commit` — zero host syncs on this path. Retraction flushes
        the pipeline first (its guard must validate eagerly against
        committed state).
        """
        with span("engine.ingest"):
            with span("engine.validate"):
                self.validate_batch(batch, retract=retract)
            self._resolve_evictions()
            self._guard_retract_rows(retract)
            if self.overlap and retract:
                self.commit()
            self._maybe_renorm_touch()
            with span("engine.pad"):
                padded = self._bucket_pad(batch)
            if self.pipeline == "fused1":
                if self.overlap and not retract:
                    return self._ingest_overlap(padded, orig=batch)
                return self._ingest_fused1(padded, retract, orig=batch)
            return self._ingest_staged(padded, retract, orig=batch)

    def _ingest_staged(self, padded: Table, retract: bool,
                       orig: Table) -> DeltaReport:
        """The multi-dispatch baselines (``pipeline="planner"`` and
        ``"unfused"``): build the delta, then merge it."""
        hi, lo, stats, gv, n_full, overflow = self._build_delta(padded)
        if self.pipeline == "planner":
            return self._ingest_fused(padded, hi, lo, stats, gv, n_full,
                                      overflow, retract, orig=orig)
        return self._ingest_unfused(padded, hi, lo, stats, gv, n_full,
                                    overflow, retract, orig=orig)

    def validate_batch(self, batch: Table, retract: bool = False) -> None:
        """Poison-batch quarantine: host-side schema/content validation of
        one streamed batch, run as the FIRST step of :meth:`ingest` —
        before any device dispatch, WAL append or state mutation — so a
        rejected batch provably leaves the committed state, the snapshot
        version, the estimate cache and any in-flight MVCC chain
        untouched, and never reaches a durable-engine journal.

        Rejected (raises :class:`PoisonBatchError`): missing or
        wrong-length columns, non-numeric dtypes, NaN/±inf outcomes,
        non-0/1 treatment indicators, non-finite covariates, and
        categorical codes outside ``[0, n_buckets)``.  Checks apply to
        VALID rows only — padding rows are masked everywhere downstream.
        The column pulls are explicit host reads of the caller's batch
        (never of in-flight engine state), so the overlap ingest path
        stays clean under ``jax.transfer_guard("disallow")`` and the
        host-sync counter."""
        del retract                      # same validation both directions
        cols = batch.columns
        missing = [c for c in self._row_cols if c not in cols]
        if missing:
            raise PoisonBatchError(f"batch is missing columns {missing}")
        n = batch.nrows
        valid = np.asarray(batch.valid)
        if valid.shape != (n,):
            raise PoisonBatchError(
                f"valid mask has shape {valid.shape}, want ({n},)")
        host = {}
        for c in self._row_cols:
            a = np.asarray(cols[c])
            if a.ndim != 1 or a.shape[0] != n:
                raise PoisonBatchError(
                    f"column {c!r} has shape {a.shape}, want ({n},)")
            if not (np.issubdtype(a.dtype, np.number)
                    or a.dtype == np.bool_):
                raise PoisonBatchError(
                    f"column {c!r} has non-numeric dtype {a.dtype}")
            host[c] = a
        v = valid.astype(bool)
        if not v.any():
            return
        y = host[self.outcome][v].astype(np.float64)
        if not np.isfinite(y).all():
            raise PoisonBatchError(
                f"non-finite outcome values in column {self.outcome!r}")
        for t in sorted(self.treatments):
            tv = host[t][v].astype(np.float64)
            if not (np.isfinite(tv).all()
                    and np.isin(tv, (0.0, 1.0)).all()):
                raise PoisonBatchError(
                    f"treatment column {t!r} must be a 0/1 indicator")
        for d, spec in self.specs.items():
            b = host[d][v].astype(np.float64)
            if not np.isfinite(b).all():
                raise PoisonBatchError(
                    f"non-finite values in covariate {d!r}")
            if spec.kind == "categorical" and (
                    (b < 0).any() or (b >= spec.n_buckets).any()):
                raise PoisonBatchError(
                    f"covariate {d!r} codes out of range "
                    f"[0, {spec.n_buckets})")

    @staticmethod
    def _bucket_pad(batch: Table) -> Table:
        """Pad a streamed batch to its power-of-two row bucket with
        invalid rows. Every engine and pipeline pads identically (the
        bucket is a pure function of the row count), so the streaming-
        propensity reservoir — whose uniform priorities depend on the
        padded draw SHAPE — stays bit-identical across engines, pipelines
        and mesh sizes; power-of-two buckets also absorb the mesh
        divisibility padding.

        Cost note: a non-bucket-sized batch pays one eager ``jnp.pad``
        per column here, OUTSIDE the fused program (the pads are async
        copies, no host sync, and invisible to the dispatch counter) —
        streams that deliver bucket-sized batches skip them entirely and
        keep the pure one-launch ingest."""
        pad = _bucket_rows(batch.nrows) - batch.nrows
        if pad == 0:
            return batch
        cols, valid = fused_mod.pad_tail(batch.columns, batch.valid, pad)
        return Table(columns=cols, valid=valid)

    # ------------------------------------------- single-dispatch pipeline
    def _view_table(self, name: str):
        """The stat table backing ``name`` (base or a view), in whichever
        layout (replicated Cuboid / PartitionedCuboid) the engine runs."""
        return self.base if name == BASE_VIEW else self.views[name].table

    def _pack_view_state(self):
        """The fused program's DONATED state pytree, built by reference
        from the engine's materialized views (zero copies)."""
        views = {}
        for name in (BASE_VIEW, *sorted(self.treatments)):
            tab = self._view_table(name)
            st = dict(hi=tab.key_hi, lo=tab.key_lo, stats=dict(tab.stats),
                      gv=tab.group_valid, touch=self._touch[name])
            if name != BASE_VIEW:
                st["keep"] = self.views[name].keep
            views[name] = st
        state = dict(views=views)
        if self.stream is not None:
            s = self.stream
            state["stream"] = dict(res=dict(s.columns), pri=s.priority,
                                   n=s.n, sums=dict(s.sums),
                                   sumsqs=dict(s.sumsqs))
        return state

    def _unpack_view_state(self, state) -> None:
        """Install a fused program's output state by reference swap. MUST
        run for every return (donation invalidated the old buffers, even
        when the program left the values unchanged)."""
        for name, st in state["views"].items():
            tab = dataclasses.replace(
                self._view_table(name), key_hi=st["hi"], key_lo=st["lo"],
                stats=st["stats"], group_valid=st["gv"])
            if name == BASE_VIEW:
                self.base = tab
            else:
                view = self.views[name]
                view.set_table(tab)
                view.keep = st["keep"]
            self._touch[name] = st["touch"]
        if "stream" in state:
            s = state["stream"]
            self.stream = dataclasses.replace(
                self.stream, columns=s["res"], priority=s["pri"], n=s["n"],
                sums=s["sums"], sumsqs=s["sumsqs"])
        self._post_state_swap()

    def _post_state_swap(self) -> None:
        """Invalidate layout-derived memos after ANY state mutation: the
        state version keys the partitioned canonical-reassembly memo
        (``_view_state``). The estimate cache is NOT version-checked —
        its validity is delta-predicate-based (:meth:`_invalidate` drops
        exactly the entries a committed delta touched, eviction clears
        it), so untouched subpopulation entries deliberately survive
        commits and keep serving with zero dispatches."""
        self._state_version += 1

    def _fused_caps(self) -> Tuple:
        return tuple(sorted(
            (name, self._view_table(name).capacity)
            for name in (BASE_VIEW, *self.treatments)))

    def _fused_view_dims(self) -> Tuple:
        return ((BASE_VIEW, tuple(self.codec.names)),
                *((t, self.views[t].dims) for t in sorted(self.treatments)))

    def _stream_names(self) -> Tuple[str, ...]:
        return self._row_cols if self.stream is not None else ()

    def _fused_program(self, retract: bool, donate: bool = True):
        mesh = self.mesh if self._mesh_ndev > 1 else None
        return fused_mod.get_fused_ingest(
            self.codec, tuple(sorted(self.specs.items())),
            tuple(sorted(self.treatments)), self._fused_view_dims(),
            self.outcome, self._fused_caps(), self._delta_cap, mesh,
            self.mesh_axis, self.use_pallas, retract, self._stream_names(),
            self.seed, donate)

    def _grow_views(self, n_merged: Dict[str, int],
                    grew: Dict[str, bool]) -> None:
        """Capacity-doubling growth between fused dispatches: pad every
        overflowing view (invalid-key padding keeps tables sorted and
        binary-searchable) so the re-dispatched program — recompiled at the
        new capacity — fits the merged table."""
        for name, g in grew.items():
            if not g:
                continue
            tab = self._view_table(name)
            new_cap = _capacity_ladder(max(n_merged[name], 2 * tab.capacity),
                                       self.granule)
            padded = cube_mod._pad_cuboid(tab, new_cap)
            pad = new_cap - tab.capacity
            if name == BASE_VIEW:
                self.base = padded
            else:
                view = self.views[name]
                view.set_table(padded)
                view.keep = jnp.pad(view.keep, (0, pad))
            self._touch[name] = jnp.pad(self._touch[name], (0, pad))

    def _ingest_fused1(self, batch: Table, retract: bool,
                       orig: Table = None) -> DeltaReport:
        """ONE compiled dispatch per steady-state batch: run the fused
        program (state donated), fetch the verdict scalars once, commit by
        reference swap. A view that outgrew its capacity, or a delta with
        more groups than the delta capacity, left the state untouched
        (the program gates its commit): grow and re-dispatch at the larger
        capacity. ``batch`` is the bucket-padded table the program
        consumes; ``orig`` the caller's batch, which row accounting
        reports."""
        orig = batch if orig is None else orig
        cols = {c: batch.columns[c] for c in self._row_cols}
        valid = batch.valid
        # explicit device_put of the host scalars: the steady-state ingest
        # must stay clean under jax.transfer_guard("disallow"), and the
        # guard treats jnp.asarray/implicit jit-arg transfers as implicit
        counter = jax.device_put(np.int32(self._ingest_count + 1))
        for _ in range(32):
            with span("engine.dispatch"):
                prog = self._fused_program(retract)
                n_batches = jax.device_put(
                    np.int32(0 if self.stream is None
                             else self.stream.n_batches))
                new_state, verdicts = prog(cols, valid,
                                           self._pack_view_state(), counter,
                                           n_batches)
                self._unpack_view_state(new_state)
            with span("engine.verdict_wait"):
                f = device_fetch(verdicts, label="ingest-verdict")
            if bool(f["overflow"]):
                with span("engine.grow"):
                    self._delta_cap = _capacity_ladder(
                        max(int(f["n_full"]), 2 * self._delta_cap),
                        self.delta_granule)
                count("ingest.redispatches")
                continue
            if retract and (not all(map(bool, f["ok"].values()))
                            or f["neg_min"] < -0.5):
                self._raise_bad_retraction()
            if not any(map(bool, f["grew"].values())):
                break
            with span("engine.grow"):
                self._grow_views(
                    {k: int(v) for k, v in f["n_merged"].items()},
                    {k: bool(v) for k, v in f["grew"].items()})
            count("ingest.redispatches")
        else:
            raise RuntimeError("fused ingest: capacity growth diverged")
        count("ingest.resort_merges",
              sum(not bool(v) for v in f["ok"].values()))
        # committed on device; mirror the host-side bookkeeping
        with span("engine.bookkeep"):
            if self.rows is not None:
                self.rows = self.rows.append(
                    orig.select(list(self.rows.table.columns)),
                    granule=self.row_granule)
            if self.stream is not None:
                self.stream = dataclasses.replace(
                    self.stream, n_batches=self.stream.n_batches + 1)
            self.n_rows_ingested += -orig.nrows if retract else orig.nrows
            self._ingest_count += 1
            invalidated = self._invalidate(
                np.asarray(f["gv"]).reshape(-1),
                lambda d: np.asarray(f["buckets"][d]).reshape(-1))
            return DeltaReport(
                n_rows=orig.nrows, n_delta_groups=int(f["n_delta"]),
                fast_path={k: bool(v) for k, v in f["ok"].items()},
                invalidated=invalidated)

    # ------------------------------------------- MVCC overlap (pipelined)
    @staticmethod
    def _start_async_fetch(tree) -> None:
        """Kick off device->host copies without blocking (the commit-time
        ``device_get`` then finds them already in flight)."""
        for leaf in jax.tree_util.tree_leaves(tree):
            start = getattr(leaf, "copy_to_host_async", None)
            if start is not None:
                start()

    def _ingest_overlap(self, batch: Table, orig: Table) -> PendingIngest:
        """Dispatch one MVCC ingest hop WITHOUT any host sync.

        The program's input is the tail of the in-flight chain (or the
        committed snapshot when the chain is empty — that first hop
        compiles with ``donate=False`` so the committed buffers stay
        alive for serving and rollback); its output becomes the new tail.
        Verdicts stay on device (async host copy started) until
        :meth:`commit`. Device-side gating makes the chain safe to run
        blind: a hop that overflowed or needed growth passes its input
        state through unchanged, so later hops always compute on a
        correct base and commit-time rollback simply replays every
        in-flight batch in order."""
        if len(self._inflight) >= self.max_inflight:
            self.commit()   # bounded pipeline depth: documented sync point
        depth = len(self._inflight)
        cols = {c: batch.columns[c] for c in self._row_cols}
        valid = batch.valid
        counter = jax.device_put(
            np.int32(self._ingest_count + depth + 1))
        n_batches = jax.device_put(np.int32(
            0 if self.stream is None else self.stream.n_batches + depth))
        src = (self._inflight[-1].state if depth
               else self._pack_view_state())
        with span("engine.dispatch"):
            prog = self._fused_program(False, donate=depth > 0)
            new_state, verdicts = prog(cols, valid, src, counter, n_batches)
            self._start_async_fetch(verdicts)
        pending = PendingIngest(self, orig.nrows)
        self._inflight.append(_InFlight(state=new_state, verdicts=verdicts,
                                        batch=batch, orig=orig,
                                        pending=pending))
        return pending

    def commit(self) -> List[DeltaReport]:
        """MVCC commit point: check every in-flight verdict with ONE
        ``device_get`` and atomically advance the committed snapshot.

        Clean chain (no delta overflow, no capacity growth): install the
        LAST in-flight state by reference swap — the intermediate states
        were consumed device-side by donation — bump the version once per
        batch, and run each batch's host bookkeeping and delta-predicate
        cache invalidation in order. Any failed hop instead ROLLS BACK to
        the committed snapshot (its buffers were never donated) and
        REPLAYS all in-flight batches synchronously in original order,
        which preserves the float merge order — every committed version
        is bitwise identical to the synchronous pipeline's. Returns the
        per-batch reports (also filled into each :class:`PendingIngest`).
        No-op when nothing is in flight."""
        entries = self._inflight
        if not entries:
            return []
        self._inflight = []
        with span("engine.verdict_wait"):
            fetched = device_fetch([e.verdicts for e in entries],
                                   label="commit")
        n_good = 0
        for f in fetched:
            if bool(f["overflow"]) or any(map(bool, f["grew"].values())):
                break
            n_good += 1
        if n_good < len(entries):
            # rollback-and-replay: the committed buffers are alive (first
            # hop never donates), every in-flight output is discarded
            reports = []
            for e in entries:
                rep = self._ingest_fused1(e.batch, False, orig=e.orig)
                e.pending.report = rep
                reports.append(rep)
            return reports
        self._unpack_view_state(entries[-1].state)   # bumps version by 1
        self._state_version += len(entries) - 1      # ... one per batch
        if self.stream is not None:
            self.stream = dataclasses.replace(
                self.stream, n_batches=self.stream.n_batches + len(entries))
        reports = []
        for e, f in zip(entries, fetched):
            count("ingest.resort_merges",
                  sum(not bool(v) for v in f["ok"].values()))
            if self.rows is not None:
                self.rows = self.rows.append(
                    e.orig.select(list(self.rows.table.columns)),
                    granule=self.row_granule)
            self.n_rows_ingested += e.orig.nrows
            self._ingest_count += 1
            invalidated = self._invalidate(
                np.asarray(f["gv"]).reshape(-1),
                lambda d, f=f: np.asarray(f["buckets"][d]).reshape(-1))
            rep = DeltaReport(
                n_rows=e.orig.nrows, n_delta_groups=int(f["n_delta"]),
                fast_path={k: bool(v) for k, v in f["ok"].items()},
                invalidated=invalidated)
            e.pending.report = rep
            reports.append(rep)
        return reports

    def snapshot_version(self) -> int:
        """The committed MVCC snapshot version queries serve RIGHT NOW.

        Settles any lazily pending eviction first (its deferred shrink
        pass is a commit), so two reads with no intervening commit are
        guaranteed equal — the serving layer's one-version-per-wave
        invariant reads this, never ``_state_version`` directly.
        In-flight overlap ingests do NOT move it; :meth:`commit` does."""
        self._resolve_evictions()
        return self._state_version

    # -------------------------------------------------- touch-stamp renorm
    def _maybe_renorm_touch(self) -> None:
        """int32 wraparound guard for the eviction stamps: when the ingest
        counter nears 2^31, shift every live stamp (and the counter) down.
        Eviction compares differences only, so TTL semantics are unchanged
        — exactly for ``ttl < TOUCH_CLAMP_AGE`` (~2^30 ingests), and
        conservatively (groups kept, never spuriously evicted) beyond.
        The threshold compare is host-integer only (sync-free); when it
        fires in overlap mode the pipeline is flushed first — the renorm
        rewrites the committed touch stamps."""
        if (self._ingest_count + len(self._inflight)
                < fused_mod.TOUCH_RENORM_LIMIT):
            return
        self.commit()
        self._renorm_touch()

    def _renorm_touch(self) -> None:
        touch = {k: np.asarray(v) for k, v in self._touch.items()}
        gvs = {name: np.asarray(self._view_table(name).group_valid)
               for name in touch}
        mins = [int(t[gvs[n]].min()) for n, t in touch.items()
                if gvs[n].any()]
        # shift by the min live stamp (exact), but at least down to
        # TOUCH_CLAMP_AGE: a cold group stamped ages ago must not pin the
        # shift at ~0 and turn renormalization into a per-ingest full
        # host sync. Stamps older than the clamp window collapse to 0 =
        # "at least TOUCH_CLAMP_AGE ingests old".
        m = min(mins + [self._ingest_count])
        m = max(m, self._ingest_count - fused_mod.TOUCH_CLAMP_AGE)
        if m <= 0:
            return
        self._touch = {
            n: self._place(jnp.asarray(
                np.where(gvs[n], np.maximum(t - m, 0), 0).astype(np.int32)))
            for n, t in touch.items()}
        self._ingest_count -= m

    def _place(self, tree):
        """State placement hook — identity for the replicated layout; the
        partitioned engine shards (P, ...) leaves over the mesh."""
        return tree

    def _commit_rows(self, batch: Table, retract: bool,
                     orig: Table = None) -> None:
        """Row log / streaming-propensity / counter updates shared by both
        ingest paths. Called only after the retraction guard has passed.
        ``batch`` is the bucket-padded table (the streaming-propensity
        update MUST see the padded draw shape — same as the fused1
        in-program update); row accounting uses ``orig``."""
        orig = batch if orig is None else orig
        if self.rows is not None:
            self.rows = self.rows.append(
                orig.select(list(self.rows.table.columns)),
                granule=self.row_granule)
        if self.stream is not None:
            self.stream = self.stream.update(
                {c: batch.columns[c] for c in self._row_cols},
                batch.valid, retract=retract)
        self.n_rows_ingested += -orig.nrows if retract else orig.nrows
        self._ingest_count += 1

    def _guard_retract_rows(self, retract: bool) -> None:
        if retract and self.rows is not None:
            raise ValueError("retract=True is not supported with "
                             "keep_rows=True (the row log is append-only)")

    def _raise_bad_retraction(self) -> None:
        raise ValueError(
            "retraction of rows that were never ingested: the delta "
            "contains unknown group keys or would drive a group count "
            "negative; engine state is unchanged")

    def _ingest_fused(self, batch: Table, hi, lo, stats, gv, n_full,
                      overflow, retract: bool,
                      orig: Table = None) -> DeltaReport:
        orig = batch if orig is None else orig
        dcap = self._delta_cap
        tnames = tuple(sorted(self.treatments))
        plan = _plan_ingest(
            hi, lo, stats, gv,
            self.base.key_hi, self.base.key_lo, self.base.stats,
            {t: self.views[t].cuboid.key_hi for t in tnames},
            {t: self.views[t].cuboid.key_lo for t in tnames},
            {t: self.views[t].cuboid.stats for t in tnames},
            {t: self.views[t].cuboid.group_valid for t in tnames},
            {t: self.views[t].keep for t in tnames},
            codec=self.codec, tnames=tnames,
            vdims=tuple(self.views[t].dims for t in tnames),
            retract=retract, use_pallas=self.use_pallas, dcap=dcap)
        # THE one host sync of a fast-path ingest: every decision at once
        fetched = device_fetch(dict(
            overflow=overflow, n_full=n_full, ok_b=plan["ok_b"],
            ok_v={t: plan["views"][t]["ok"] for t in tnames},
            neg_min=plan["neg_min"], n_delta=plan["n_delta"],
            gv=plan["gv"], buckets=plan["buckets"]))
        fetched["overflow"] = bool(fetched["overflow"]) or (
            int(fetched["n_full"]) > dcap)
        d_hi, d_lo = plan["d_keys"]
        d_gv = plan["gv"]
        if fetched["overflow"]:
            # the sliced delta missed groups: fall back to the exact
            # host-compacted path and grow the delta capacity geometrically
            self._delta_cap = _capacity_ladder(
                max(int(n_full), 2 * self._delta_cap), self.delta_granule)
            return self._ingest_unfused(batch, hi, lo, stats, gv, n_full,
                                        overflow, retract, orig=orig)
        all_fast = bool(fetched["ok_b"]) and all(
            bool(v) for v in fetched["ok_v"].values())
        if retract and (not all_fast or fetched["neg_min"] < -0.5):
            self._raise_bad_retraction()
        counter = self._ingest_count + 1
        fast: Dict[str, bool] = {}
        d_base = cube_mod.Cuboid(
            codec=self.codec, key_hi=d_hi, key_lo=d_lo,
            stats=plan["d_stats"], group_valid=d_gv, treatments=tnames)
        if fetched["ok_b"]:
            old = self.base
            self.base = dataclasses.replace(old, stats=plan["merged_b"])
            self._touch[BASE_VIEW] = _stamp_touch(
                self._touch[BASE_VIEW], plan["pos_b"], d_gv, counter)
        else:
            old = self.base
            self.base, pos_b, _ = cube_mod.merge_delta(
                old, d_base, granule=self.granule,
                use_pallas=self.use_pallas, fast=False)
            self._touch[BASE_VIEW] = _stamp_touch(
                _remap_touch(old, self.base, self._touch[BASE_VIEW]),
                pos_b, d_gv, counter)
        fast[BASE_VIEW] = bool(fetched["ok_b"])
        for t in tnames:
            view = self.views[t]
            vplan = plan["views"][t]
            v_gv = vplan["delta"][3]
            if fetched["ok_v"][t]:
                view.cuboid = dataclasses.replace(view.cuboid,
                                                  stats=vplan["stats"])
                view.keep = vplan["keep"]
                self._touch[t] = _stamp_touch(self._touch[t], vplan["pos"],
                                              v_gv, counter)
            else:
                v_hi, v_lo, v_stats, _ = vplan["delta"]
                d_view = cube_mod.Cuboid(
                    codec=view.cuboid.codec, key_hi=v_hi, key_lo=v_lo,
                    stats=v_stats, group_valid=v_gv, treatments=tnames)
                old_v = view.cuboid
                merged, pos_v, _ = cube_mod.merge_delta(
                    old_v, d_view, granule=self.granule,
                    use_pallas=self.use_pallas, fast=False)
                nt = merged.stats[f"t_{t}"]
                view.keep = overlap_keep(merged.group_valid, nt,
                                         merged.stats["one"] - nt)
                view.cuboid = merged
                self._touch[t] = _stamp_touch(
                    _remap_touch(old_v, merged, self._touch[t]),
                    pos_v, v_gv, counter)
            fast[t] = bool(fetched["ok_v"][t])
        self._commit_rows(batch, retract, orig=orig)
        self._post_state_swap()
        invalidated = self._invalidate(
            fetched["gv"], lambda d: fetched["buckets"][d])
        return DeltaReport(n_rows=orig.nrows,
                           n_delta_groups=int(fetched["n_delta"]),
                           fast_path=fast, invalidated=invalidated)

    def _ingest_unfused(self, batch: Table, hi, lo, stats, gv, n_full,
                        overflow, retract: bool,
                        orig: Table = None) -> DeltaReport:
        """Legacy merge loop: one blocking device->host read per merge (the
        fast/slow decision), plus host-side delta compaction. Kept as the
        exact fallback for delta-capacity overflow and as the measurable
        baseline for the fused path (``bench_online.py``)."""
        orig = batch if orig is None else orig
        tnames = tuple(sorted(self.treatments))
        if bool(overflow):
            # a local shard overflowed: the gathered table is incomplete,
            # so rebuild the delta exactly on one device
            d_base = cube_mod.delta_cuboid(batch, self.specs, tnames,
                                           self.outcome,
                                           granule=self.delta_granule)
        else:
            d_base = cube_mod.compact_cuboid(
                cube_mod.Cuboid(codec=self.codec, key_hi=hi, key_lo=lo,
                                stats=stats, group_valid=gv,
                                treatments=tnames),
                granule=self.delta_granule)
        if retract:
            d_base = dataclasses.replace(
                d_base, stats={k: -v for k, v in d_base.stats.items()})
        fast: Dict[str, bool] = {}
        merged_base, pos_b, fast_b = cube_mod.merge_delta(
            self.base, d_base, granule=self.granule,
            use_pallas=self.use_pallas)
        if retract:
            counts = np.stack(
                [np.asarray(merged_base.stats["one"])]
                + [np.asarray(merged_base.stats[f"t_{t}"]) for t in tnames])
            if not fast_b or counts.min() < -0.5:
                self._raise_bad_retraction()
        counter = self._ingest_count + 1
        old_base = self.base
        self.base, fast[BASE_VIEW] = merged_base, fast_b
        touch_b = (self._touch[BASE_VIEW] if fast_b else
                   _remap_touch(old_base, merged_base,
                                self._touch[BASE_VIEW]))
        self._touch[BASE_VIEW] = _stamp_touch(touch_b, pos_b,
                                              d_base.group_valid, counter)
        # lattice propagation: the delta itself rolls up to each view's dims
        for t, view in self.views.items():
            d_view = cube_mod.compact_cuboid(
                cube_mod.rollup(d_base, view.dims),
                granule=self.delta_granule)
            old_v = view.cuboid
            merged, pos, was_fast = cube_mod.merge_delta(
                old_v, d_view, granule=self.granule,
                use_pallas=self.use_pallas)
            nt = merged.stats[f"t_{t}"]
            nc = merged.stats["one"] - nt
            if was_fast:
                # O(|delta groups|): flip only the touched groups
                view.keep = update_overlap(view.keep, merged.group_valid,
                                           nt, nc, pos)
            else:
                view.keep = overlap_keep(merged.group_valid, nt, nc)
            view.cuboid = merged
            touch_v = (self._touch[t] if was_fast else
                       _remap_touch(old_v, merged, self._touch[t]))
            self._touch[t] = _stamp_touch(touch_v, pos,
                                          d_view.group_valid, counter)
            fast[t] = was_fast
        self._commit_rows(batch, retract, orig=orig)
        self._post_state_swap()
        gv_host = np.asarray(d_base.group_valid)
        buckets: Dict[str, np.ndarray] = {}

        def dim_buckets(dim: str) -> np.ndarray:
            if dim not in buckets:
                buckets[dim] = np.asarray(self.codec.extract(
                    d_base.key_hi, d_base.key_lo, dim))
            return buckets[dim]

        invalidated = self._invalidate(gv_host, dim_buckets)
        return DeltaReport(n_rows=orig.nrows,
                           n_delta_groups=int(np.sum(gv_host)),
                           fast_path=fast, invalidated=invalidated)

    def _invalidate(self, gv: np.ndarray,
                    dim_buckets: Callable[[str], np.ndarray]) -> Tuple:
        """Drop exactly the cache entries whose group predicate the delta
        touched: an unrestricted estimate is touched by any delta; a
        sub-population estimate only if some delta group satisfies its
        (conjunctive) bucket predicate. Operates on host arrays the caller
        already fetched — no extra device sync."""
        if not gv.any():
            return ()
        dropped: List[Tuple] = []
        for key in list(self._cache):
            _, subpop = key
            if subpop is None:
                touched = True
            else:
                sat = gv.copy()
                for dim, allowed in subpop:
                    sat &= np.isin(dim_buckets(dim), list(allowed))
                touched = bool(sat.any())
            if touched:
                dropped.append(key)
                del self._cache[key]
        return tuple(dropped)

    # ----------------------------------------------------------- eviction
    def _evict_n_parts(self) -> int:
        """Partition count handed to the fused eviction program: 0 marks
        the replicated (C,) layout, >0 the (P, C) partitioned one."""
        return 0

    def evict(self, ttl: int) -> EvictReport:
        """Drop every group whose last delta touch is more than ``ttl``
        ingests old — the bounded-state escape hatch for streams whose key
        space grows without bound. Estimates afterwards cover only the
        surviving (recently active) groups, so this deliberately trades
        the offline-equivalence guarantee for bounded memory; re-ingesting
        an evicted key later resurrects it as a fresh group.

        Runs as ONE donated device program over every view (per-partition
        compaction kernels on the partitioned layout — no host round trip
        per view; the compaction is an exact re-sort GATHER at the current
        capacity, so surviving stats are bit-identical). When the live
        occupancy of a view falls below 1/4 of its (grown) capacity, a
        shrink pass slices the compacted tables down to a halved-or-
        smaller capacity and the next ingest recompiles at the smaller
        capacity — long-lived streams whose live set collapses
        reclaim device memory (``state_bytes()`` decreases).

        Returns a LAZY :class:`EvictReport` ({view name: groups evicted}):
        the count scalars are fetched — and the estimate-cache
        invalidation is applied, scoped to the views with NONZERO evicted
        counts — at the engine's next sync point or on first access,
        whichever comes first, so this call never stalls behind an
        in-flight ingest dispatch. In overlap mode the pipeline is
        committed first (eviction rewrites the committed snapshot)."""
        self.commit()
        self._resolve_evictions()
        mesh = self.mesh if self._mesh_ndev > 1 else None
        prog = fused_mod.get_fused_evict(
            tuple(sorted(self.treatments)), self._fused_caps(),
            self._evict_n_parts(), mesh, self.mesh_axis,
            self.stream is not None)
        new_state, counts, live = prog(
            self._pack_view_state(),
            jax.device_put(np.int32(self._ingest_count - ttl)))
        self._unpack_view_state(new_state)
        self._start_async_fetch((counts, live))
        report = EvictReport(self)
        self._pending_evict = (counts, live, report)
        return report

    def _resolve_evictions(self) -> None:
        """Settle a lazily pending :meth:`evict`: ONE ``device_get`` for
        the count/occupancy scalars, then the cache invalidation scoped
        to views that actually lost groups (untouched-view entries keep
        serving at zero dispatches — evicting only the base view never
        drops a treatment-view estimate) and the deferred capacity-shrink
        pass. Every cache probe, ingest, commit and state accessor calls
        this first, so no stale entry is ever served and the next
        dispatch compiles against settled shapes. Idempotent no-op when
        nothing is pending."""
        if self._pending_evict is None:
            return
        counts, live, report = self._pending_evict
        self._pending_evict = None
        fetched = device_fetch(dict(counts=counts, live=live),
                               label="evict")
        evicted = {k: int(v) for k, v in fetched["counts"].items()}
        report._counts = evicted
        touched = {name for name, n in evicted.items() if n}
        if touched:
            for key in list(self._cache):
                if key[0] in touched:
                    del self._cache[key]
        self._maybe_shrink({k: int(v) for k, v in fetched["live"].items()})

    # ------------------------------------------------ capacity shrink pass
    def _shrink_granule(self) -> int:
        """Capacity floor of the shrink pass (per partition when the
        layout is partitioned)."""
        return self.granule

    def _shrink_view(self, name: str, new_cap: int) -> None:
        """Slice one view's compacted tables (valid groups are a sorted
        prefix after eviction, so slicing is lossless) down to
        ``new_cap`` slots."""
        tab = self._view_table(name)
        sliced = cube_mod.slice_cuboid(tab, new_cap)
        if name == BASE_VIEW:
            self.base = sliced
        else:
            view = self.views[name]
            view.set_table(sliced)
            view.keep = view.keep[:new_cap]
        self._touch[name] = self._touch[name][:new_cap]

    def _maybe_shrink(self, live_max: Dict[str, int]) -> None:
        """Reclaim capacity after eviction: when a view's live occupancy
        (max per partition on the (P, C) layout) fell below 1/4 of its
        grown capacity, compact into a halved-or-smaller capacity (floor:
        the allocation granule, headroom: 2x live rounded up) so the next
        fused dispatch recompiles at the smaller shape and device memory
        is actually returned."""
        shrunk = False
        for name, live in live_max.items():
            cap = self._view_table(name).capacity
            gran = self._shrink_granule()
            if cap <= gran or 4 * live > cap:
                continue
            new_cap = _capacity_ladder(2 * live, gran)
            if new_cap >= cap:
                continue
            self._shrink_view(name, new_cap)
            shrunk = True
        if shrunk:
            self._post_state_swap()

    # ------------------------------------------------------------ queries
    def _view_state(self, treatment: str
                    ) -> Tuple[cube_mod.Cuboid, jnp.ndarray]:
        """(stat table, overlap mask) an ``assemble``-path query runs on —
        the replicated view directly; the partitioned engine overrides
        this with the canonical cross-partition reassembly (one compiled
        dispatch, memoized per state version)."""
        view = self.views[treatment]
        return view.cuboid, view.keep

    def _fused_estimate(self, treatment: str,
                        subpopulation: SubPop) -> ATEEstimate:
        """One-dispatch fused query over the RAW materialized state (both
        layouts; shard_map body on a mesh), answered as a one-spec wave
        of the batched query program. The spec is DATA, so one compiled
        program per view schema and capacity serves every
        subpopulation: a program with the predicate in its trace
        compiles once per distinct subpopulation, and on the TPU
        compiler a program that sorts 2^16 or more slots takes tens of
        seconds to compile. Returns host scalars (one fetch inside)."""
        return self._batched_estimate(
            [self._normalize_spec((treatment, subpopulation))])[0]

    def _estimate(self, treatment: str, subpopulation: SubPop,
                  pipeline: str = None) -> ATEEstimate:
        """Uncached estimate through the chosen query pipeline (host
        scalars from "fused", device scalars from "assemble"). Both
        pipelines share the canonical estimator body, so they return
        bit-identical results — the differential harness cross-checks
        them against the oracle on every stream."""
        pipeline = pipeline or self.query_pipeline
        if pipeline == "fused":
            return self._fused_estimate(treatment, subpopulation)
        cub, keep = self._view_state(treatment)
        return _estimate_view(cub, keep, treatment, subpopulation)

    def ate(self, treatment: str, subpopulation: SubPop = None
            ) -> ATEEstimate:
        """Online causal query from materialized state: ONE compiled
        dispatch + one scalar-sized ``device_get`` (the fused query
        program — subpopulation filter, keep mask and canonical reduction
        all in-program, per-partition/1-per-device work on a mesh), or the
        ``assemble`` baseline when selected. Repeated queries hit the
        host-resident cache with ZERO dispatches and zero transfers;
        validity is delta-predicate-based (a committed batch drops
        exactly the entries whose subpopulation it touched, eviction
        clears the cache — see :meth:`_invalidate`).
        Includes the Neyman within-group variance, carried by the cuboid's
        second-moment (``yy``) stat columns. Estimates are a deterministic
        function of the canonical (key-sorted) group content alone, so
        identical maintained stats give bit-identical results regardless
        of engine layout, query pipeline or mesh size (see
        :func:`_estimate_view`). For a WINDOW of heterogeneous queries
        use :meth:`ate_batch` (one dispatch for all of them, same cache,
        bitwise-identical answers)."""
        self._resolve_evictions()
        key = (treatment, _freeze_subpop(subpopulation))
        if key in self._cache:
            self.cache_hits += 1
            return self._cache[key]
        self.cache_misses += 1
        est = self._estimate(treatment, subpopulation)
        # THE one host sync of an uncached query: every scalar at once
        # (inside the batched path for "fused"). state_version tags the
        # committed MVCC snapshot this estimate was computed at (a cache
        # hit keeps the version it was COMPUTED at — the entry surviving
        # later commits means the delta predicate proved those commits
        # did not touch it).
        if self.query_pipeline != "fused":
            est = ATEEstimate(**device_fetch(dict(
                ate=est.ate, att=est.att,
                n_matched_treated=est.n_matched_treated,
                n_matched_control=est.n_matched_control,
                n_groups=est.n_groups, variance=est.variance),
                label="query"), state_version=self._state_version)
        self._cache[key] = est
        return est

    def cached_estimate(self, treatment: str, subpopulation: SubPop = None
                        ) -> Optional[ATEEstimate]:
        """Cache-only probe: the host-resident estimate for this query if
        one is live, else None — NEVER dispatches (a lazily pending
        eviction is settled first, so a stale entry for an evicted view
        can never be served). The serving layer uses this so cache hits
        are answered without occupying a batch slot."""
        self._resolve_evictions()
        return self._cache.get((treatment, _freeze_subpop(subpopulation)))

    # ------------------------------------------------- batched query path
    def _spec_cards(self) -> Tuple:
        """The engine's base-dim ``(dim, cardinality)`` schema — the
        static word layout every encoded query spec of this engine shares
        (:func:`repro.core.fused.spec_word_layout`)."""
        return tuple((d, self.specs[d].n_buckets) for d in sorted(self.specs))

    def _batch_view_schema(self) -> Tuple:
        """Views in view-id order as ``(treatment, codec)`` — the static
        half of the batched query program's cache key."""
        return tuple((t, self.views[t].table.codec)
                     for t in sorted(self.treatments))

    def _view_query_args(self, treatment: str) -> Tuple:
        """One view's raw state in the batched program's layout: keys,
        ROLE-ordered stat columns, group validity, overlap keep."""
        view = self.views[treatment]
        tab = view.table
        stats = tuple(tab.stats[k]
                      for k in fused_mod.query_stat_names(treatment))
        return (tab.key_hi, tab.key_lo, stats, tab.group_valid, view.keep)

    def _batch_query_flags(self) -> Tuple:
        """(mesh, mesh_axis, partitioned) the batched program compiles
        under — replicated views never shard the query."""
        return None, self.mesh_axis, False

    def _normalize_spec(self, spec) -> Tuple[str, Tuple, int]:
        """Accept a ``QuerySpec``-shaped object (``treatment``,
        ``subpopulation``, optional ``estimand`` attributes) or a plain
        ``(treatment, subpopulation)`` pair; returns (treatment, frozen
        subpop, estimand id) and validates against the schema."""
        if isinstance(spec, tuple):
            treatment, sub = spec
            estimand = "ate"
        else:
            treatment = spec.treatment
            sub = spec.subpopulation
            estimand = getattr(spec, "estimand", "ate")
        if treatment not in self.treatments:
            raise KeyError(f"unknown treatment {treatment!r}")
        if estimand not in fused_mod.ESTIMAND_IDS:
            raise ValueError(f"unknown estimand {estimand!r}")
        frozen = _freeze_subpop(sub)
        if frozen:
            vdims = set(self.views[treatment].dims)
            bad = [d for d, _ in frozen if d not in vdims]
            if bad:
                raise ValueError(
                    f"subpopulation dims {bad} not materialized in view "
                    f"{treatment!r} (dims {sorted(vdims)}); add them to "
                    f"query_dims")
        return treatment, frozen, fused_mod.ESTIMAND_IDS[estimand]

    def _batched_estimate(self, keys: Sequence[Tuple[str, Tuple, int]]
                          ) -> List[ATEEstimate]:
        """Uncached batched estimate: encode the specs into the device
        spec table, pad to the pow2 spec bucket, run ONE compiled batched
        query dispatch, fetch the ``(B,)`` scalar vectors with one
        ``device_get``. Bitwise identical per spec to the B=1 fused
        path (shared canonical estimator body + padding-invariant
        canonical reduce)."""
        cards = self._spec_cards()
        view_ids = {t: i for i, t in enumerate(sorted(self.treatments))}
        rows = [fused_mod.encode_query_spec(cards, view_ids[t], est, sub)
                for t, sub, est in keys]
        bucket = _bucket_specs(len(rows))
        width = rows[0].shape[0]
        table = np.zeros((bucket, width), np.uint32)
        table[:len(rows)] = np.stack(rows)
        mesh, mesh_axis, partitioned = self._batch_query_flags()
        prog = fused_mod.get_fused_query_batch(
            self._batch_view_schema(), cards, bucket, mesh, mesh_axis,
            partitioned)
        states = tuple(self._view_query_args(t)
                       for t in sorted(self.treatments))
        out = device_fetch(prog(states, jnp.asarray(table)), label="query")
        record_batch(len(rows), label="query")
        return [ATEEstimate(
            ate=out["ate"][i], att=out["att"][i],
            n_matched_treated=out["n_matched_treated"][i],
            n_matched_control=out["n_matched_control"][i],
            n_groups=out["n_groups"][i], variance=out["variance"][i],
            state_version=self._state_version)
            for i in range(len(rows))]

    def ate_batch(self, specs: Sequence) -> List[ATEEstimate]:
        """Answer MANY heterogeneous causal queries with at most ONE
        compiled dispatch. ``specs`` mixes treatments (view choice),
        subpopulation predicates and estimands freely — each is encoded
        into a fixed-width device-resident spec row
        (:func:`repro.core.fused.encode_query_spec`) and the whole batch
        runs through the batched query program
        (:func:`repro.core.fused.get_fused_query_batch`), padded to a
        pow2 spec bucket so batch-size jitter never retraces.

        Cache integration mirrors :meth:`ate`: specs whose
        ``(treatment, subpopulation)`` estimate is cached are answered
        host-side with zero dispatches; identical in-flight specs in one
        batch window are DEDUPED to a single slot (``batch_deduped``
        counts the collapsed duplicates — e.g. many dashboards asking the
        same question); every computed estimate lands in the same cache,
        with the same delta-predicate invalidation on later ingests.
        Results are bitwise identical to B sequential uncached
        :meth:`ate` calls, in input order. Each element of ``specs`` is a
        ``QuerySpec``-shaped object or a ``(treatment, subpopulation)``
        pair."""
        self._resolve_evictions()
        resolved = [self._normalize_spec(s) for s in specs]
        out: List[Optional[ATEEstimate]] = [None] * len(resolved)
        miss_keys: List[Tuple[str, Tuple, int]] = []
        slot_of: Dict[Tuple, int] = {}
        slot_idx: List[Tuple[int, int]] = []   # (spec index, slot)
        for i, (t, sub, est) in enumerate(resolved):
            cache_key = (t, sub)
            hit = self._cache.get(cache_key)
            if hit is not None:
                self.cache_hits += 1
                out[i] = hit
                continue
            slot = slot_of.get(cache_key)
            if slot is None:
                slot = len(miss_keys)
                slot_of[cache_key] = slot
                miss_keys.append((t, sub, est))
                self.cache_misses += 1
            else:
                self.batch_deduped += 1
            slot_idx.append((i, slot))
        if miss_keys:
            results = self._batched_estimate(miss_keys)
            for (t, sub, _), est in zip(miss_keys, results):
                self._cache[(t, sub)] = est
            for i, slot in slot_idx:
                out[i] = results[slot]
        return out

    def cem_groups(self, treatment: str) -> CEMGroups:
        """Current CEM group stats with the incrementally maintained
        overlap mask (same shape the offline path produces)."""
        self._resolve_evictions()
        cub, keep = self._view_state(treatment)
        nt = cub.stats[f"t_{treatment}"]
        nc = cub.stats["one"] - nt
        yt = cub.stats[f"yt_{treatment}"]
        dummy = groupby.Grouping(
            perm=jnp.zeros((cub.capacity,), jnp.int32),
            inv_perm=jnp.zeros((cub.capacity,), jnp.int32),
            seg_ids=jnp.zeros((cub.capacity,), jnp.int32),
            group_hi=cub.key_hi, group_lo=cub.key_lo,
            group_valid=cub.group_valid, n_groups=cub.n_groups())
        return CEMGroups(grouping=dummy, keep=keep, n_treated=nt,
                         n_control=nc, sum_y_t=yt,
                         sum_y_c=cub.stats["y"] - yt)

    def _rowlookup_query(self, treatment: str):
        """(program, state args) of the one-dispatch row lookup over the
        RAW materialized state (replicated layout: broadcast binary
        search; partitioned override: per-partition probe, routed over the
        mesh)."""
        view = self.views[treatment]
        tab = view.table
        vspecs = tuple(sorted((d, self.specs[d]) for d in view.dims))
        prog = fused_mod.get_fused_rowlookup(tab.codec, vspecs, 0, None,
                                             self.mesh_axis)
        return prog, (tab.key_hi, tab.key_lo, view.keep)

    def matched_rows(self, treatment: str, table: Table,
                     pipeline: str = None) -> jnp.ndarray:
        """Row-level matched mask for ``table`` against current state.

        The fused pipeline (default) runs coarsen + pack + lookup + keep
        mask as ONE compiled dispatch straight on the materialized state;
        on the partitioned layout each probe row hashes to its owning
        partition and binary-searches only that partition's table — on a
        mesh via the ROUTED lookup (one all-to-all out, local search, one
        all-to-all back), so no device ever reassembles the view. The
        ``assemble`` baseline keeps the broadcast-table search of the
        planner era. Both return identical masks (exact boolean
        semantics)."""
        self._resolve_evictions()
        pipeline = pipeline or self.query_pipeline
        if pipeline == "assemble":
            cub, keep = self._view_state(treatment)
            vspecs = {d: self.specs[d] for d in self.views[treatment].dims}
            _, hi, lo = pack_keys(table, vspecs, codec=cub.codec)
            pos, found = groupby.lookup_rows_in_table(
                hi, lo, cub.key_hi, cub.key_lo)
            return table.valid & found & keep[pos]
        prog, state_args = self._rowlookup_query(treatment)
        cols = {d: table.columns[d] for d in self.views[treatment].dims}
        return prog(cols, table.valid, *state_args)

    # --------------------------------------------------------- propensity
    def refresh_propensity(self, treatment: str, features: Sequence[str],
                           step_budget: int = 4, cold_iters: int = 32,
                           ridge: float = 1e-4) -> LogisticModel:
        """(Re)fit the propensity model: a cold Newton fit the first time,
        afterwards warm-started from the previous coefficients with
        ``step_budget`` iterations. With ``keep_rows=True`` the fit runs
        over the full row log; otherwise it runs over the engine's
        streaming sufficient statistics — the bounded uniform reservoir
        for rows, standardized by the exact stream-wide moment
        accumulators — so no unbounded row log is ever needed."""
        prev = self.models.get(treatment)
        n_iter = step_budget if prev is not None else cold_iters
        if self.rows is not None:
            tbl = self.rows.table
            X = design_matrix(tbl, features)
            model = fit_logistic(X, tbl[treatment], tbl.valid,
                                 n_iter=n_iter, ridge=ridge, init=prev)
        elif self.stream is not None:
            cols, rvalid = self.stream.reservoir()
            X = jnp.stack([cols[f] for f in features], axis=-1)
            # stream-exact moments standardize the COLD fit; warm refits
            # keep the previous model's frozen standardization so the
            # coefficients stay in one basis across refreshes
            moments = (self.stream.moments(features) if prev is None
                       else None)
            model = fit_logistic(X, cols[treatment], rvalid,
                                 n_iter=n_iter, ridge=ridge, init=prev,
                                 moments=moments)
        else:
            raise ValueError("refresh_propensity needs keep_rows=True or "
                             "reservoir_size > 0")
        self.models[treatment] = model
        return model

    # ------------------------------------------- durability (canonical)
    def schema_fingerprint(self) -> str:
        """Stable description of the engine's coarsening schema — a
        checkpoint taken under one fingerprint only restores into engines
        with the SAME fingerprint (layout/partition count/mesh are free to
        differ; the schema is not)."""
        return repr((tuple(sorted(self.specs.items())),
                     tuple(sorted(self.treatments.items())), self.outcome,
                     tuple(sorted(self.query_dims)), self.seed,
                     0 if self.stream is None else self.stream.capacity))

    def export_canonical(self) -> dict:
        """Layout-free snapshot of the committed engine state, on host.

        Every view is exported as its CANONICAL content: the valid groups
        (including exactly-retracted zero-count groups — they are live
        groups and dropping them would change later fast/slow merge
        decisions), globally key-sorted, with their stat columns, overlap
        keep, and touch stamps; plus the streaming-propensity reservoir,
        the optional row log, the estimate cache and the version/counter
        scalars.  Because estimates are functions of canonical group
        content alone, this snapshot restores into ANY engine layout —
        replicated or partitioned at any ``n_parts``/device count — with
        bit-identical queries (:meth:`install_canonical`).

        Commits the in-flight MVCC chain first (a checkpoint is a commit
        barrier) and fetches the committed buffers with ONE labeled
        ``device_fetch`` — the sync lives HERE, never on the ingest path.
        """
        self.commit()
        self._resolve_evictions()
        tnames = tuple(sorted(self.treatments))
        fetch = {}
        for name in (BASE_VIEW, *tnames):
            tab = self._view_table(name)
            entry = dict(hi=tab.key_hi, lo=tab.key_lo,
                         stats=dict(tab.stats), gv=tab.group_valid,
                         touch=self._touch[name])
            if name != BASE_VIEW:
                entry["keep"] = self.views[name].keep
            fetch[name] = entry
        if self.stream is not None:
            s = self.stream
            fetch["__stream__"] = dict(res=dict(s.columns), pri=s.priority,
                                       n=s.n, sums=dict(s.sums),
                                       sumsqs=dict(s.sumsqs))
        if self.rows is not None:
            fetch["__rows__"] = dict(cols=dict(self.rows.table.columns),
                                     valid=self.rows.table.valid)
        host = device_fetch(fetch, label="checkpoint")
        views = {}
        for name in (BASE_VIEW, *tnames):
            h = host[name]
            gv = np.asarray(h["gv"]).reshape(-1).astype(bool)
            hi = np.asarray(h["hi"]).reshape(-1)[gv]
            lo = np.asarray(h["lo"]).reshape(-1)[gv]
            order = np.lexsort((lo, hi))
            view = dict(
                hi=np.ascontiguousarray(hi[order]),
                lo=np.ascontiguousarray(lo[order]),
                touch=np.ascontiguousarray(
                    np.asarray(h["touch"]).reshape(-1)[gv][order]),
                stats={k: np.ascontiguousarray(
                    np.asarray(c).reshape(-1)[gv][order])
                    for k, c in h["stats"].items()})
            if name != BASE_VIEW:
                view["keep"] = np.ascontiguousarray(
                    np.asarray(h["keep"]).reshape(-1)[gv][order])
            views[name] = view
        snap = dict(views=views, scalars=dict(
            state_version=int(self._state_version),
            ingest_count=int(self._ingest_count),
            n_rows_ingested=int(self.n_rows_ingested),
            delta_cap=int(self._delta_cap)))
        if self.stream is not None:
            hs = host["__stream__"]
            snap["stream"] = dict(
                res={k: np.asarray(a) for k, a in hs["res"].items()},
                pri=np.asarray(hs["pri"]), n=np.asarray(hs["n"]),
                sums={k: np.asarray(a) for k, a in hs["sums"].items()},
                sumsqs={k: np.asarray(a)
                        for k, a in hs["sumsqs"].items()},
                n_batches=int(self.stream.n_batches),
                capacity=int(self.stream.capacity))
        if self.rows is not None:
            hr = host["__rows__"]
            used = self.rows.used
            snap["rows"] = dict(
                cols={k: np.asarray(a)[:used]
                      for k, a in hr["cols"].items()},
                valid=np.asarray(hr["valid"])[:used])
        snap["cache"] = tuple(
            (t, sub, dict(ate=e.ate, att=e.att,
                          n_matched_treated=e.n_matched_treated,
                          n_matched_control=e.n_matched_control,
                          n_groups=e.n_groups, variance=e.variance,
                          state_version=int(e.state_version)))
            for (t, sub), e in sorted(self._cache.items(),
                                      key=lambda kv: repr(kv[0])))
        snap["fingerprint"] = self.schema_fingerprint()
        return snap

    def install_canonical(self, snap: dict) -> None:
        """Install an :meth:`export_canonical` snapshot into THIS engine.

        The engine must be freshly constructed (nothing ingested) with
        the same schema fingerprint; its layout is free to differ from
        the exporter's — the per-view install hook (:meth:`_install_view`)
        re-materializes the canonical content under the local layout
        (replicated: one padded sorted table; partitioned: scattered to
        owner partitions by key hash, sorted per partition), and the
        bit-identity contract makes every query agree with the exporting
        engine bitwise."""
        if snap.get("fingerprint") != self.schema_fingerprint():
            raise ValueError(
                "checkpoint schema mismatch: snapshot fingerprint "
                f"{snap.get('fingerprint')!r} != engine "
                f"{self.schema_fingerprint()!r}")
        if self._ingest_count or self.n_rows_ingested or self._inflight:
            raise ValueError(
                "install_canonical requires a freshly constructed engine")
        tnames = tuple(sorted(self.treatments))
        for name in (BASE_VIEW, *tnames):
            self._install_view(name, snap["views"][name])
        stream = snap.get("stream")
        if (stream is None) != (self.stream is None):
            raise ValueError("snapshot/engine reservoir config mismatch "
                             "(reservoir_size)")
        if stream is not None:
            self.stream = dataclasses.replace(
                self.stream,
                columns={k: jnp.asarray(a)
                         for k, a in stream["res"].items()},
                priority=jnp.asarray(stream["pri"]),
                n=jnp.asarray(stream["n"]),
                sums={k: jnp.asarray(a)
                      for k, a in stream["sums"].items()},
                sumsqs={k: jnp.asarray(a)
                        for k, a in stream["sumsqs"].items()},
                n_batches=int(stream["n_batches"]))
        rows = snap.get("rows")
        if (rows is None) != (self.rows is None):
            raise ValueError("snapshot/engine row-log config mismatch "
                             "(keep_rows)")
        if rows is not None:
            self.rows = GrowableTable.from_table(
                Table.from_numpy(dict(rows["cols"]),
                                 np.asarray(rows["valid"])),
                granule=self.row_granule)
        self._cache = {}
        for t, sub, est in snap.get("cache", ()):
            key = (t, _freeze_subpop(sub) if sub else None)
            self._cache[key] = ATEEstimate(**est)
        sc = snap["scalars"]
        self._ingest_count = int(sc["ingest_count"])
        self.n_rows_ingested = int(sc["n_rows_ingested"])
        self._delta_cap = int(sc["delta_cap"])
        self._state_version = int(sc["state_version"])

    def _install_view(self, name: str, v: dict) -> None:
        """Re-materialize one canonical view under the replicated layout:
        valid groups as a sorted prefix, invalid-key padding to the
        capacity the ladder gives (:func:`_capacity_ladder`, the rule live
        growth follows, so a recovered engine reuses the live engine's
        compiled programs)."""
        from repro.core.keys import INVALID_HI, INVALID_LO
        tab = self._view_table(name)
        n = int(np.asarray(v["hi"]).shape[0])
        cap = _capacity_ladder(n, self.granule)
        hi = np.full((cap,), INVALID_HI, np.uint32)
        lo = np.full((cap,), INVALID_LO, np.uint32)
        gv = np.zeros((cap,), bool)
        hi[:n], lo[:n], gv[:n] = v["hi"], v["lo"], True
        stats = {}
        for k, col in v["stats"].items():
            a = np.zeros((cap,), np.asarray(col).dtype)
            a[:n] = col
            stats[k] = jnp.asarray(a)
        cub = dataclasses.replace(
            tab, key_hi=jnp.asarray(hi), key_lo=jnp.asarray(lo),
            stats=stats, group_valid=jnp.asarray(gv))
        touch = np.zeros((cap,), np.int32)
        touch[:n] = v["touch"]
        if name == BASE_VIEW:
            self.base = cub
        else:
            view = self.views[name]
            view.set_table(cub)
            keep = np.zeros((cap,), bool)
            keep[:n] = v["keep"]
            view.keep = jnp.asarray(keep)
        self._touch[name] = jnp.asarray(touch)

    # -------------------------------------------------------------- state
    def stats(self) -> Dict[str, Dict[str, int]]:
        """Materialized-state summary (for benchmarks and demos)."""
        self._resolve_evictions()
        out = {BASE_VIEW: {"capacity": self.base.capacity,
                           "n_groups": int(self.base.n_groups())}}
        for t, view in self.views.items():
            out[t] = {"capacity": view.cuboid.capacity,
                      "n_groups": int(view.cuboid.n_groups()),
                      "n_matched_groups": int(jnp.sum(
                          view.keep.astype(jnp.int32)))}
        return out

    def _state_arrays(self) -> List[jnp.ndarray]:
        """Every array of the materialized views — `self.base` and
        `view.table` have the same field names in both the replicated and
        the partitioned layouts, so one walk serves both engines."""
        arrs = [self.base.key_hi, self.base.key_lo, self.base.group_valid,
                *self.base.stats.values()]
        for view in self.views.values():
            tab = view.table
            arrs += [tab.key_hi, tab.key_lo, tab.group_valid,
                     *tab.stats.values(), view.keep]
        arrs += list(self._touch.values())
        return arrs

    @staticmethod
    def _per_device_bytes(a) -> int:
        shards = getattr(a, "addressable_shards", None)
        if shards:
            return max(int(s.data.nbytes) for s in shards)
        return int(a.nbytes)

    def state_bytes(self) -> Dict[str, int]:
        """Resident bytes of the materialized views (keys + stats + masks
        + touch stamps): ``total`` across the job and ``per_device`` (the
        largest per-device share — equal to ``total`` when views are
        replicated, ~``total / n_parts`` when partitioned over a mesh)."""
        self._resolve_evictions()
        arrs = self._state_arrays()
        return {"total": sum(int(a.nbytes) for a in arrs),
                "per_device": sum(self._per_device_bytes(a) for a in arrs)}


class PartitionedOnlineEngine(OnlineEngine):
    """Online engine whose MATERIALIZED views are key-range partitioned.

    The replicated :class:`OnlineEngine` shards the per-batch delta BUILD
    over a mesh but keeps every merged stat table fully replicated, so
    total materialized state is capped by one chip's memory. Here the
    tables themselves are split into contiguous ranges of a hashed key
    space (:func:`repro.core.cube.partition_ids`): every view is a
    ``(n_parts, capacity)`` :class:`repro.core.cube.PartitionedCuboid`
    whose leading axis is sharded over the mesh's data axis, deltas are
    ROUTED to owner devices (one all-to-all on key range,
    :func:`repro.core.distributed.make_routed_delta_build`, instead of
    all-gather-everything), and merges, overlap maintenance, eviction and
    compaction run per partition. Per-device resident state is ~1/N of the
    total (``state_bytes()``).

    Queries run straight on the partitioned state
    (``query_pipeline="fused"``, the default): ``ate()`` is one compiled
    dispatch whose per-partition masking is device-local and whose
    canonical reduction is capacity/partition-count invariant, and
    ``matched_rows()`` is a routed row lookup (hash probes to owner
    partitions, all-to-all, partition-local binary search) — no full
    reassembly anywhere, and every result bit-identical to the replicated
    engine's on any device count. ``query_pipeline="assemble"`` keeps the
    planner-era reassembly baseline
    (:func:`repro.core.cube.unpartition_view`, memoized per state
    version), which ``cem_groups()`` also serves from. Batched queries
    (:meth:`OnlineEngine.ate_batch`) shard the same way: the one batched
    dispatch all-gathers each view's raw tables once (state-sized
    traffic, independent of the batch size) and runs the replicated
    batched estimator, bit-identical to the replicated engine's batch.

    n_parts: number of key-range partitions. With a mesh attached it must
    be a MULTIPLE of the data-axis size: each device owns
    ``k = n_parts / N`` contiguous hash ranges (k-partitions-per-device),
    so per-partition capacity — and with it the unit of growth and
    compaction — is bounded independently of the mesh size. Without a
    mesh, any ``n_parts >= 1`` runs the same layout on a single device
    (the differential test harness exercises this). All other arguments
    match :class:`OnlineEngine`; ``fused_host_sync=False`` /
    ``pipeline="unfused"`` are not supported (the partitioned path is
    fused-only).

    Ingest (:meth:`OnlineEngine.ingest`) folds each batch into every
    partitioned view: route the delta to owner partitions, merge, flip and
    stamp per partition, fetch ONE fused verdict. ``pipeline="fused1"``
    (default) does all of it, routing included, in one donated compiled
    dispatch; "planner" keeps the two-dispatch path (:meth:`_ingest_staged`).
    Semantics (the retraction guard, delta-overflow handling, the
    ``overlap=True`` MVCC protocol) match the replicated engine's bit for
    bit.
    """

    def __init__(self, specs: Mapping[str, CoarsenSpec],
                 treatments: Mapping[str, Sequence[str]], outcome: str,
                 n_parts: int = None, **kwargs):
        # consumed by _init_state, which super().__init__ invokes once the
        # mesh attributes exist — so only partitioned tables are ever
        # allocated, never a throwaway replicated layout
        self._requested_n_parts = n_parts
        super().__init__(specs, treatments, outcome, **kwargs)
        if not self.fused_host_sync:
            raise ValueError("PartitionedOnlineEngine is fused-only")

    def _init_state(self) -> None:
        n_parts = self._requested_n_parts
        if self.mesh is not None and self._mesh_ndev > 1:
            if n_parts is None:
                n_parts = self._mesh_ndev
            if n_parts % self._mesh_ndev != 0:
                raise ValueError(
                    f"n_parts={n_parts} must be a multiple of the mesh "
                    f"data-axis size {self._mesh_ndev} (k contiguous "
                    f"partitions per device)")
        self.n_parts = 1 if n_parts is None else int(n_parts)
        if self.n_parts < 1:
            raise ValueError(f"n_parts must be >= 1, got {self.n_parts}")
        # per-partition capacity granule: hashing balances groups across
        # partitions, so each holds ~1/n_parts of the keys — capacities
        # (hence per-device bytes) shrink with the partition count
        self._part_granule = max(64, -(-self.granule // self.n_parts))
        tnames = tuple(sorted(self.treatments))
        self.base = self._place(cube_mod.stack_partitions(
            [cube_mod.empty_cuboid(self.codec, tnames,
                                   capacity=self._part_granule)
             for _ in range(self.n_parts)]))
        self.views: Dict[str, _PartView] = {}
        for t, dims, vcodec in self._view_schema():
            self.views[t] = _PartView(
                treatment=t, dims=dims,
                pcub=self._place(cube_mod.stack_partitions(
                    [cube_mod.empty_cuboid(vcodec, tnames,
                                           capacity=self._part_granule)
                     for _ in range(self.n_parts)])),
                keep=self._place(
                    jnp.zeros((self.n_parts, self._part_granule), bool)))
        self._touch = {name: self._place(
            jnp.zeros((self.n_parts, self._part_granule), jnp.int32))
            for name in (BASE_VIEW, *tnames)}
        self._routed_builds: Dict[int, Callable] = {}
        # treatment -> (state version, canonical cuboid, keep): the
        # assemble-path / cem_groups reassembly memo
        self._assembled: Dict[str, Tuple] = {}

    # ----------------------------------------------------- state placement
    def _place(self, tree):
        """Shard (P, ...) state over the mesh's data axis (one partition
        per device); identity on a single device."""
        if self.mesh is None or self._mesh_ndev == 1:
            return tree
        from repro.launch.mesh import shard_partitions
        return shard_partitions(self.mesh, tree, axis=self.mesh_axis)

    # ------------------------------------------------------- delta build
    def _get_routed_build(self, capacity: int) -> Callable:
        if capacity not in self._routed_builds:
            from repro.core.distributed import make_routed_delta_build
            view_dims = {BASE_VIEW: tuple(self.codec.names)}
            for t in sorted(self.treatments):
                view_dims[t] = self.views[t].dims
            self._routed_builds[capacity] = make_routed_delta_build(
                self.mesh, self.specs, sorted(self.treatments),
                self.outcome, capacity, view_dims, axis=self.mesh_axis,
                n_parts=self.n_parts)
        return self._routed_builds[capacity]

    def _route_from_base(self, hi, lo, stats, gv):
        """Single-device routing: regroup a base-granularity delta stat
        table into per-partition tables for every view (each view routes
        by ITS OWN key hash — rollup changes the key, hence the owner)."""
        deltas = {BASE_VIEW: cube_mod.route_delta(hi, lo, stats, gv,
                                                  self.n_parts)}
        for t in sorted(self.treatments):
            roll = cube_mod._rollup_fn(self.codec, self.views[t].dims)
            vhi, vlo, vstats, vgv = roll(hi, lo, gv, stats)
            deltas[t] = cube_mod.route_delta(vhi, vlo, vstats, vgv,
                                             self.n_parts)
        return deltas

    def _build_delta_parts(self, batch: Table):
        """Routed delta stat tables of one batch: (deltas, n_full,
        overflow) where deltas[name] is (hi, lo, stats, gv) with leading
        (n_parts, delta_capacity) axes."""
        cols = {c: batch.columns[c] for c in self._row_cols}
        valid = batch.valid
        if self.mesh is not None and self._mesh_ndev > 1:
            cols, valid = fused_mod.pad_tail(
                cols, valid, (-batch.nrows) % self._mesh_ndev)
            fn = self._get_routed_build(self._delta_cap)
            return fn(cols, valid)
        fn = cube_mod._build_fn(self.codec,
                                tuple(sorted(self.specs.items())),
                                tuple(sorted(self.treatments)), self.outcome)
        hi, lo, stats, gv = fn(cols, valid)
        n_full = jnp.sum(gv.astype(jnp.int32))
        dcap = self._delta_cap
        deltas = self._route_from_base(hi[:dcap], lo[:dcap],
                                       {k: v[:dcap] for k, v in
                                        stats.items()}, gv[:dcap])
        return deltas, n_full, n_full > dcap

    # ------------------------------------------------------------- ingest
    def _ingest_staged(self, padded: Table, retract: bool,
                       orig: Table) -> DeltaReport:
        deltas, n_full, overflow = self._build_delta_parts(padded)
        return self._ingest_parts(padded, deltas, n_full, overflow, retract,
                                  orig=orig)

    # --------------------------------------- single-dispatch (fused1) hooks
    def _fused_program(self, retract: bool, donate: bool = True):
        mesh = self.mesh if self._mesh_ndev > 1 else None
        return fused_mod.get_fused_ingest_parts(
            self.codec, tuple(sorted(self.specs.items())),
            tuple(sorted(self.treatments)), self._fused_view_dims(),
            self.outcome, self._fused_caps(), self._delta_cap,
            self.n_parts, mesh, self.mesh_axis, self.use_pallas, retract,
            self._stream_names(), self.seed, donate)

    def _grow_views(self, n_merged: Dict[str, int],
                    grew: Dict[str, bool]) -> None:
        """Per-partition capacity doubling: pad every (P, C) array of an
        overflowing view along the slot axis (keys stay sorted — invalid
        padding is the largest key) and let the next dispatch recompile at
        the new per-partition capacity."""
        for name, g in grew.items():
            if not g:
                continue
            tab = self._view_table(name)
            new_cap = _capacity_ladder(max(n_merged[name], 2 * tab.capacity),
                                       self._part_granule)
            padded = self._place(cube_mod.pad_partitioned(tab, new_cap))
            pad = new_cap - tab.capacity
            if name == BASE_VIEW:
                self.base = padded
            else:
                view = self.views[name]
                view.set_table(padded)
                view.keep = self._place(
                    jnp.pad(view.keep, ((0, 0), (0, pad))))
            self._touch[name] = self._place(
                jnp.pad(self._touch[name], ((0, 0), (0, pad))))

    def _evict_n_parts(self) -> int:
        return self.n_parts

    def _ingest_parts(self, batch: Table, deltas, n_full, overflow,
                      retract: bool, orig: Table = None) -> DeltaReport:
        orig = batch if orig is None else orig
        tnames = tuple(sorted(self.treatments))
        with span("engine.dispatch"):
            plan = _plan_ingest_parts(
                deltas, self.base.key_hi, self.base.key_lo, self.base.stats,
                {t: self.views[t].pcub.key_hi for t in tnames},
                {t: self.views[t].pcub.key_lo for t in tnames},
                {t: self.views[t].pcub.stats for t in tnames},
                {t: self.views[t].pcub.group_valid for t in tnames},
                {t: self.views[t].keep for t in tnames},
                codec=self.codec, tnames=tnames, retract=retract,
                use_pallas=self.use_pallas)
        # THE one host sync of a fast-path ingest
        with span("engine.verdict_wait"):
            fetched = device_fetch(dict(
                overflow=overflow, ok=plan["ok"], neg_min=plan["neg_min"],
                n_delta=plan["n_delta"], gv=deltas[BASE_VIEW][3],
                buckets=plan["buckets"]))
        if fetched["overflow"]:
            # a routed table was truncated: rebuild the delta exactly on
            # the host, grow the capacity geometrically, and re-route
            with span("engine.grow"):
                self._delta_cap = _capacity_ladder(
                    max(int(n_full), 2 * self._delta_cap),
                    self.delta_granule)
                d = cube_mod.delta_cuboid(batch, self.specs, tnames,
                                          self.outcome,
                                          granule=self._delta_cap)
                deltas = self._route_from_base(d.key_hi, d.key_lo,
                                               dict(d.stats), d.group_valid)
            count("ingest.redispatches")
            return self._ingest_parts(batch, deltas, n_full,
                                      jnp.asarray(False), retract,
                                      orig=orig)
        all_fast = all(bool(v) for v in fetched["ok"].values())
        if retract and (not all_fast or fetched["neg_min"] < -0.5):
            self._raise_bad_retraction()
        counter = self._ingest_count + 1
        with span("engine.dispatch"):
            fast = self._merge_parts(deltas, plan, fetched, counter)
        with span("engine.bookkeep"):
            self._commit_rows(batch, retract, orig=orig)
            self._post_state_swap()
            invalidated = self._invalidate(
                fetched["gv"].reshape(-1),
                lambda d: fetched["buckets"][d].reshape(-1))
            return DeltaReport(n_rows=orig.nrows,
                               n_delta_groups=int(fetched["n_delta"]),
                               fast_path=fast, invalidated=invalidated)

    def _merge_parts(self, deltas, plan, fetched,
                     counter: int) -> Dict[str, bool]:
        """Per-view merges of the staged partitioned path, each on the
        fast path the plan verdicts allow; returns view -> fast path."""
        fast: Dict[str, bool] = {}
        for name in (BASE_VIEW, *sorted(self.treatments)):
            ok = bool(fetched["ok"][name])
            d_hi, d_lo, d_stats, d_gv = deltas[name]
            pcub = (self.base if name == BASE_VIEW
                    else self.views[name].pcub)
            if ok:
                merged = dataclasses.replace(pcub,
                                             stats=plan["merged"][name])
                self._touch[name] = _stamp_touch_parts(
                    self._touch[name], plan["pos"][name], d_gv, counter)
            else:
                merged, pos = cube_mod.merge_delta_parts(
                    pcub, d_hi, d_lo, d_stats, d_gv,
                    granule=self._part_granule)
                merged = self._place(merged)
                self._touch[name] = _stamp_touch_parts(
                    self._place(_remap_touch_parts(pcub, merged,
                                                   self._touch[name])),
                    pos, d_gv, counter)
            if name == BASE_VIEW:
                self.base = merged
            else:
                view = self.views[name]
                if ok:
                    view.keep = plan["keep"][name]
                else:
                    nt = merged.stats[f"t_{name}"]
                    view.keep = overlap_keep(merged.group_valid, nt,
                                             merged.stats["one"] - nt)
                view.pcub = merged
            fast[name] = ok
        return fast

    # ------------------------------------------- durability (canonical)
    def _install_view(self, name: str, v: dict) -> None:
        """Re-materialize one canonical view under the partitioned layout:
        scatter the globally key-sorted groups to their owner partitions
        (the owner is the same pure key-hash function deltas route by, so
        a replicated checkpoint restores into ANY ``n_parts``), keep each
        partition's slice sorted (global key order restricted to one
        partition stays sorted — partition ids are monotone in the key),
        and pad every partition to one shared capacity on the ladder."""
        from repro.core.keys import INVALID_HI, INVALID_LO
        tab = self._view_table(name)
        hi_c = np.asarray(v["hi"], np.uint32)
        lo_c = np.asarray(v["lo"], np.uint32)
        n = int(hi_c.shape[0])
        P = self.n_parts
        if n:
            pid = np.asarray(cube_mod.partition_ids(hi_c, lo_c, P))
            counts = np.bincount(pid, minlength=P)
        else:
            pid = np.zeros((0,), np.int64)
            counts = np.zeros((P,), np.int64)
        cap = _capacity_ladder(int(counts.max()), self._part_granule)
        hi = np.full((P, cap), INVALID_HI, np.uint32)
        lo = np.full((P, cap), INVALID_LO, np.uint32)
        gv = np.zeros((P, cap), bool)
        touch = np.zeros((P, cap), np.int32)
        keep = np.zeros((P, cap), bool)
        stats = {k: np.zeros((P, cap), np.asarray(c).dtype)
                 for k, c in v["stats"].items()}
        for p in range(P):
            idx = np.nonzero(pid == p)[0]
            k = len(idx)
            if not k:
                continue
            hi[p, :k], lo[p, :k], gv[p, :k] = hi_c[idx], lo_c[idx], True
            touch[p, :k] = np.asarray(v["touch"])[idx]
            for sk, c in v["stats"].items():
                stats[sk][p, :k] = np.asarray(c)[idx]
            if name != BASE_VIEW:
                keep[p, :k] = np.asarray(v["keep"])[idx]
        pcub = self._place(dataclasses.replace(
            tab, key_hi=jnp.asarray(hi), key_lo=jnp.asarray(lo),
            stats={k: jnp.asarray(a) for k, a in stats.items()},
            group_valid=jnp.asarray(gv)))
        if name == BASE_VIEW:
            self.base = pcub
        else:
            view = self.views[name]
            view.set_table(pcub)
            view.keep = self._place(jnp.asarray(keep))
        self._touch[name] = self._place(jnp.asarray(touch))

    # ------------------------------------------------ capacity shrink pass
    def _shrink_granule(self) -> int:
        return self._part_granule

    def _shrink_view(self, name: str, new_cap: int) -> None:
        tab = self._view_table(name)
        sliced = self._place(cube_mod.slice_partitioned(tab, new_cap))
        if name == BASE_VIEW:
            self.base = sliced
        else:
            view = self.views[name]
            view.set_table(sliced)
            view.keep = self._place(view.keep[:, :new_cap])
        self._touch[name] = self._place(self._touch[name][:, :new_cap])

    # ------------------------------------------------------------ queries
    def _view_state(self, treatment: str
                    ) -> Tuple[cube_mod.Cuboid, jnp.ndarray]:
        """Canonical reassembly of a partitioned view in ONE compiled
        dispatch (:func:`repro.core.cube.unpartition_view`): flatten the
        (tiny) per-partition stat vectors, re-sort by key, recompute
        overlap from the (exact) stats. Memoized per STATE VERSION — the
        memo survives until the next committed mutation, so dashboards
        repeating ``cem_groups``/assemble-path queries pay zero extra
        dispatches."""
        entry = self._assembled.get(treatment)
        if entry is None or entry[0] != self._state_version:
            pv = self.views[treatment]
            cub, keep = cube_mod.unpartition_view(pv.pcub, treatment)
            entry = (self._state_version, cub, keep)
            self._assembled[treatment] = entry
        return entry[1], entry[2]

    def _batch_query_flags(self) -> Tuple:
        """Batched queries run straight on the (P, C) partitioned state:
        on a mesh the batched program all_gathers each view's raw
        partition tables once inside its shard_map body and reduces
        replicated (bit-identical to the replicated engine)."""
        mesh = self.mesh if self._mesh_ndev > 1 else None
        return mesh, self.mesh_axis, True

    def _rowlookup_query(self, treatment: str):
        """Partitioned row lookup: hash each probe row to its owning
        partition, binary-search only that partition's table — ROUTED over
        the mesh (all-to-all out and back) when one is attached."""
        view = self.views[treatment]
        tab = view.pcub
        mesh = self.mesh if self._mesh_ndev > 1 else None
        vspecs = tuple(sorted((d, self.specs[d]) for d in view.dims))
        prog = fused_mod.get_fused_rowlookup(tab.codec, vspecs,
                                             self.n_parts, mesh,
                                             self.mesh_axis)
        return prog, (tab.key_hi, tab.key_lo, view.keep)

    # -------------------------------------------------------------- state
    def stats(self) -> Dict[str, Dict[str, int]]:
        """Materialized-state summary; capacities are PER PARTITION."""
        self._resolve_evictions()
        out = {BASE_VIEW: {"capacity": self.base.capacity,
                           "n_parts": self.n_parts,
                           "n_groups": int(self.base.n_groups())}}
        for t, view in self.views.items():
            out[t] = {"capacity": view.pcub.capacity,
                      "n_parts": self.n_parts,
                      "n_groups": int(view.pcub.n_groups()),
                      "n_matched_groups": int(jnp.sum(
                          view.keep.astype(jnp.int32)))}
        return out
