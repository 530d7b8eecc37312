"""Distributed ZaliQL: CEM/ATE and k-NN matching across a device mesh.

Two TPU-native communication patterns replace the single-node SQL engine
(design rationale in DESIGN.md §2):
COMBINE-BROADCAST GROUP-BY (CEM, subclassification, cuboids):
  1. each device groups its row shard locally (sort + segment stats — the
     paper's Fig. 5 view, per shard);
  2. the fixed-capacity local stat tables are `all_gather`ed over the data
     axis (stats are tiny relative to rows: #groups << #rows);
  3. every device re-combines the gathered tables (same group-by code) and
     now holds the REPLICATED global group stats -> overlap filter, ATE,
     AWMD are pure local math;
  4. row-level matched masks come from looking each row's key up in the
     broadcast table (binary search).
  Rows never move: no skew, no repartition, deterministic. Collective cost
  = capacity * n_stats * 4B per device, independent of data size.

RING k-NN JOIN (NNM):
  control shards circulate around the data axis via `ppermute` (ring-
  attention style) while each device folds every visiting shard into its
  queries' running top-k — the same merge loop as the knn_topk Pallas
  kernel, so compute overlaps the ring transfer on real hardware.

Both are shard_map programs over a 1-D "data" axis (the flattened
(pod, data) axes of the production mesh).
"""
from __future__ import annotations

from typing import Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import groupby
from repro.core.matching import BIG, _topk_merge
from repro.launch.trace import hot_path

#: contract-lint scoping (tools/contract_check.py): this module is
#: engine-owned — dispatch/donation rules ZQL001-ZQL006 apply.
__engine_owned__ = True


# ===================== combine-broadcast group-by ===========================
@hot_path
def _local_stat_table(hi, lo, stats: Dict[str, jnp.ndarray], capacity: int,
                      single_word: bool = False):
    g = groupby.group_by_key(hi, lo, single_word=single_word)
    sums = groupby.segment_sums(g, stats)
    return (g.group_hi[:capacity], g.group_lo[:capacity],
            {k: v[:capacity] for k, v in sums.items()},
            g.n_groups > capacity)


@hot_path
def _combine_gathered(ghi, glo, gstats: Dict[str, jnp.ndarray],
                      capacity: int, single_word: bool = False):
    """ghi/glo: (n_dev * capacity,) gathered keys (with invalid padding);
    re-group and sum."""
    g = groupby.group_by_key(ghi, glo, single_word=single_word)
    sums = groupby.segment_sums(g, gstats)
    return (g.group_hi[:capacity], g.group_lo[:capacity],
            {k: v[:capacity] for k, v in sums.items()},
            g.n_groups > capacity)


def make_distributed_cem(mesh, capacity: int = 8192,
                         axis: str = "data", key_bits: int = 64):
    """Returns a jitted function
        f(hi, lo, t, y, valid) -> (ate, att, variance, n_groups,
                                   n_matched_t, n_matched_c, matched_valid,
                                   overflow)
    with rows sharded over `axis` and scalar outputs replicated.

    The per-group state is the SAME decomposable stat schema the cube and
    the online engine materialize (``cube.stat_names`` for one treatment
    named "t": one/y/yy + t_t/yt_t/yyt_t, via ``cube.delta_stat_columns``)
    and the estimate comes from the shared
    :func:`repro.core.ate.estimate_ate_from_stats` — one definition of
    group stats and of the estimator across the offline cube, the online
    engine and the distributed path. The ``yy`` second moments make the
    Neyman within-group variance a free extra output.
    """
    from repro.core import cube as cube_mod
    from repro.core.ate import estimate_ate_from_stats
    from repro.core.cem import overlap_keep
    from repro.core.keys import INVALID_HI, INVALID_LO

    single_word = key_bits <= 31

    def shard_body(hi, lo, t, y, valid):
        stats = cube_mod.delta_stat_columns({"t": t, "y": y}, valid,
                                            ("t",), "y")
        lhi, llo, lstats, loverflow = _local_stat_table(
            hi, lo, stats, capacity, single_word=single_word)
        # gather stat tables from every device (tiny vs rows)
        ghi = jax.lax.all_gather(lhi, axis, tiled=True)
        glo = jax.lax.all_gather(llo, axis, tiled=True)
        gstats = {k: jax.lax.all_gather(v, axis, tiled=True)
                  for k, v in lstats.items()}
        chi, clo, cstats, coverflow = _combine_gathered(
            ghi, glo, gstats, capacity, single_word=single_word)
        gvalid = ~((chi == INVALID_HI) & (clo == INVALID_LO))
        nt = cstats["t_t"]
        nc = cstats["one"] - nt
        keep = overlap_keep(gvalid, nt, nc)
        yt = cstats["yt_t"]
        yc = cstats["y"] - yt
        est = estimate_ate_from_stats(
            keep, nt, nc, yt, yc,
            sum_yy_t=cstats["yyt_t"], sum_yy_c=cstats["yy"] - cstats["yyt_t"])
        # row-level matched mask: look up each local row in the (sorted)
        # global table
        pos, found = groupby.lookup_rows_in_table(hi, lo, chi, clo)
        matched = valid & found & keep[pos]
        overflow = loverflow | coverflow
        any_overflow = jax.lax.pmax(overflow.astype(jnp.int32), axis) > 0
        return (est.ate, est.att, est.variance, est.n_groups,
                est.n_matched_treated, est.n_matched_control, matched,
                any_overflow)

    fn = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P(), P(), P(), P(), P(axis), P()),
        check_vma=False)
    from repro.launch.trace import counted_jit
    return counted_jit(fn)


# ===================== sharded online delta build ===========================
@hot_path
def _sharded_delta_body(columns, valid, *, codec, specs, treatments,
                        outcome, capacity, axis):
    """Per-device shard body of the sharded (replicated-views) delta build:
    coarsen/pack/locally-aggregate the row shard, truncate to ``capacity``,
    all-gather the tiny per-device tables, re-combine. Exposed standalone so
    the fused single-dispatch ingest program (``repro.core.fused``) can
    compose it under one jit; :func:`make_sharded_delta_build` wraps it for
    the standalone (planner-path) dispatch."""
    from repro.core import cube as cube_mod
    from repro.core.coarsen import coarsen_columns

    buckets = coarsen_columns(columns, specs)
    hi, lo = codec.pack(buckets, valid)
    cols = cube_mod.delta_stat_columns(columns, valid, treatments, outcome)
    lhi, llo, lstats, loverflow = _local_stat_table(hi, lo, cols, capacity)
    ghi = jax.lax.all_gather(lhi, axis, tiled=True)
    glo = jax.lax.all_gather(llo, axis, tiled=True)
    gstats = {k: jax.lax.all_gather(v, axis, tiled=True)
              for k, v in lstats.items()}
    # full-length re-combine: the gathered table is tiny, so no second
    # truncation (hence no combine-side overflow) is needed
    g = groupby.group_by_key(ghi, glo)
    sums = groupby.segment_sums(g, gstats)
    any_overflow = jax.lax.pmax(loverflow.astype(jnp.int32), axis) > 0
    return (g.group_hi, g.group_lo, sums, g.group_valid, g.n_groups,
            any_overflow)


def make_sharded_delta_build(mesh, specs: Mapping, treatments: Sequence[str],
                             outcome: str, capacity: int,
                             axis: str = "data"):
    """Delta-cuboid build for the ONLINE engine, sharded over ``axis``.

    Each device coarsens/packs/locally-aggregates its row shard of a
    streamed batch (the same stat schema as ``cube._build_fn``, via
    ``cube.delta_stat_columns``), truncates its local stat table to
    ``capacity`` slots, and the tiny per-device tables are ``all_gather``ed
    and re-combined with the existing combine-broadcast group-by — so every
    device ends up holding the REPLICATED global delta stat table and the
    downstream cuboid merge is identical to the single-chip path.

    Returns a jitted ``f(columns, valid) -> (hi, lo, stats, group_valid,
    n_groups, overflow)`` with rows sharded over ``axis`` and the combined
    table (length n_dev * capacity, valid groups first) replicated.
    ``overflow`` is set when any LOCAL shard had more distinct groups than
    ``capacity`` (the combined table is then incomplete and the caller must
    fall back to an exact host-side build).
    """
    import functools

    from repro.core.cem import make_codec

    codec = make_codec(specs)
    body = functools.partial(_sharded_delta_body, codec=codec,
                             specs=dict(specs),
                             treatments=tuple(treatments), outcome=outcome,
                             capacity=capacity, axis=axis)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis)),
                       out_specs=(P(), P(), P(), P(), P(), P()),
                       check_vma=False)
    from repro.launch.trace import counted_jit
    return counted_jit(fn)


# ===================== routed (partitioned) delta build =====================
@hot_path
def _routed_delta_body(columns, valid, *, codec, specs, treatments, outcome,
                       capacity, view_items, n_parts, n_dev, axis):
    """Per-device shard body of the routed delta build, generalized to
    ``n_parts = k * n_dev`` key-range partitions (k contiguous ranges per
    device). Per view: roll the local stat table up to the view's dims,
    bucket rows by OWNER DEVICE (``partition_ids(...) // k`` — partitions
    are contiguous hash ranges, so a device's k partitions are one
    contiguous range too), exchange buckets with one ``all_to_all``, then
    re-group what arrived into the k local partition tables. Exposed
    standalone so the fused single-dispatch ingest composes it; wrapped by
    :func:`make_routed_delta_build` for standalone dispatch."""
    from repro.core import cube as cube_mod
    from repro.core.coarsen import coarsen_columns
    from repro.core.keys import INVALID_HI, INVALID_LO

    base_name = view_items[0][0]
    k = n_parts // n_dev
    me = jax.lax.axis_index(axis)

    buckets = coarsen_columns(columns, specs)
    hi, lo = codec.pack(buckets, valid)
    cols = cube_mod.delta_stat_columns(columns, valid, treatments, outcome)
    lhi, llo, lstats, overflow = _local_stat_table(hi, lo, cols, capacity)
    lgv = ~((lhi == INVALID_HI) & (llo == INVALID_LO))
    deltas = {}
    n_full = jnp.int32(0)
    for name, dims in view_items:
        if name == base_name:
            vhi, vlo, vstats, vgv = lhi, llo, lstats, lgv
        else:
            roll = cube_mod._rollup_fn(codec, dims)
            vhi, vlo, vstats, vgv = roll(lhi, llo, lgv, lstats)
        # bucket by owner DEVICE, exchange buckets with one all-to-all
        pid = cube_mod.partition_ids(vhi, vlo, n_parts)
        dev = pid // jnp.int32(k)
        own = vgv[None, :] & (dev[None, :] == jnp.arange(n_dev)[:, None])
        bhi = jnp.where(own, vhi[None, :], INVALID_HI)
        blo = jnp.where(own, vlo[None, :], INVALID_LO)
        bstats = {c: jnp.where(own, v[None, :], 0.0)
                  for c, v in vstats.items()}
        rhi = jax.lax.all_to_all(bhi, axis, 0, 0, tiled=True).reshape(-1)
        rlo = jax.lax.all_to_all(blo, axis, 0, 0, tiled=True).reshape(-1)
        rstats = {c: jax.lax.all_to_all(v, axis, 0, 0,
                                        tiled=True).reshape(-1)
                  for c, v in bstats.items()}
        # re-group arrivals into the k LOCAL partition tables (partition
        # ownership is a pure function of the key, recomputed on arrival)
        rgv = ~((rhi == INVALID_HI) & (rlo == INVALID_LO))
        rpid = cube_mod.partition_ids(rhi, rlo, n_parts)
        parts_hi, parts_lo, parts_gv = [], [], []
        parts_stats = {c: [] for c in rstats}
        n_view = jnp.int32(0)
        for j in range(k):
            ownj = rgv & (rpid == me * k + j)
            phi = jnp.where(ownj, rhi, INVALID_HI)
            plo = jnp.where(ownj, rlo, INVALID_LO)
            g = groupby.group_by_key(phi, plo)
            sums = groupby.segment_sums(
                g, {c: jnp.where(ownj, v, 0.0) for c, v in rstats.items()})
            overflow = overflow | (g.n_groups > capacity)
            n_view = n_view + g.n_groups
            parts_hi.append(g.group_hi[:capacity])
            parts_lo.append(g.group_lo[:capacity])
            parts_gv.append(g.group_valid[:capacity])
            for c in rstats:
                parts_stats[c].append(sums[c][:capacity])
        if name == base_name:
            n_full = jax.lax.psum(n_view, axis)
        deltas[name] = (jnp.stack(parts_hi), jnp.stack(parts_lo),
                        {c: jnp.stack(v) for c, v in parts_stats.items()},
                        jnp.stack(parts_gv))
    any_overflow = jax.lax.pmax(overflow.astype(jnp.int32), axis) > 0
    return deltas, n_full, any_overflow


def make_routed_delta_build(mesh, specs: Mapping, treatments: Sequence[str],
                            outcome: str, capacity: int,
                            view_dims: Mapping[str, Sequence[str]],
                            axis: str = "data", n_parts: int = None):
    """Delta build for PARTITIONED materialized views: instead of
    all-gathering every per-device stat table to every device (the
    replicated path), each delta row is ROUTED to the device that owns its
    key-range partition via one all-to-all. ``n_parts`` (default: the
    data-axis size) may be any multiple of the device count — each device
    then owns ``k = n_parts / n_dev`` contiguous key ranges.

    ``view_dims`` maps view name -> dims; the FIRST entry is the base view
    and must list every dim (the others roll up from it). Returns a jitted
    ``f(columns, valid) -> (deltas, n_full, overflow)`` where
    ``deltas[name]`` is ``(hi, lo, stats, group_valid)`` with leading
    ``(n_parts, capacity)`` partition axes sharded over ``axis``,
    ``n_full`` is the total distinct base-granularity delta groups, and
    ``overflow`` means some local or routed table was truncated (caller
    must fall back to the exact host build)."""
    import functools

    from repro.core import cube as cube_mod
    from repro.core.cem import make_codec

    codec = make_codec(specs)
    n_dev = int(mesh.shape[axis])
    if n_parts is None:
        n_parts = n_dev
    if n_parts % n_dev != 0:
        raise ValueError(f"n_parts={n_parts} must be a multiple of the "
                         f"data-axis size {n_dev}")
    view_items = tuple((name, tuple(dims))
                       for name, dims in view_dims.items())
    if set(view_items[0][1]) != set(codec.names):
        raise ValueError("first view_dims entry must cover every dim")
    body = functools.partial(_routed_delta_body, codec=codec,
                             specs=dict(specs),
                             treatments=tuple(treatments), outcome=outcome,
                             capacity=capacity, view_items=view_items,
                             n_parts=n_parts, n_dev=n_dev, axis=axis)

    part = P(axis, None)
    out_deltas = {name: (part, part,
                         {k: part for k in cube_mod.stat_names(treatments)},
                         part)
                  for name, _ in view_items}
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis)),
                       out_specs=(out_deltas, P(), P()),
                       check_vma=False)
    from repro.launch.trace import counted_jit
    return counted_jit(fn)


# ===================== routed row lookup (partitioned views) ================
@hot_path
def _routed_lookup_body(columns, valid, t_hi, t_lo, keep, *, codec, specs,
                        n_parts, n_dev, axis):
    """Per-device shard body of the ROUTED row lookup: the device-resident
    ``matched_rows`` probe against key-range partitioned views with no
    full reassembly. Each device packs its row shard's (coarsened) keys,
    hashes every row to its OWNER partition, exchanges probe keys with one
    ``all_to_all``, answers the arrivals with a partition-local binary
    search in its k resident tables (``groupby.lookup_rows_in_parts``) and
    routes the boolean answers back with a second ``all_to_all`` — so
    RESIDENT state stays ~1/N per device and no device ever materializes
    the whole view. Probe buffers are currently dense per destination
    (each device searches all n_dev * n_local arrival slots, most of
    them masked invalid), so per-device probe COMPUTE is O(total probe
    rows); compacting probes per destination before routing is the
    documented ROADMAP follow-up. Exposed standalone so the fused query
    programs (:func:`repro.core.fused.get_fused_rowlookup`) compose it
    under one jit."""
    from repro.core import cube as cube_mod
    from repro.core.coarsen import coarsen_columns
    from repro.core.keys import INVALID_HI, INVALID_LO

    k = n_parts // n_dev
    me = jax.lax.axis_index(axis)
    buckets = coarsen_columns(columns, specs)
    hi, lo = codec.pack(buckets, valid)
    pid = cube_mod.partition_ids(hi, lo, n_parts)
    dev = pid // jnp.int32(k)
    own = valid[None, :] & (dev[None, :] == jnp.arange(n_dev)[:, None])
    bhi = jnp.where(own, hi[None, :], INVALID_HI)
    blo = jnp.where(own, lo[None, :], INVALID_LO)
    rhi = jax.lax.all_to_all(bhi, axis, 0, 0, tiled=True).reshape(-1)
    rlo = jax.lax.all_to_all(blo, axis, 0, 0, tiled=True).reshape(-1)
    rvalid = ~((rhi == INVALID_HI) & (rlo == INVALID_LO))
    rpid = cube_mod.partition_ids(rhi, rlo, n_parts)
    j = jnp.clip(rpid - me * jnp.int32(k), 0, k - 1)
    pos, found = groupby.lookup_rows_in_parts(rhi, rlo, j, t_hi, t_lo)
    matched = rvalid & found & keep[j, pos]
    back = jax.lax.all_to_all(matched.reshape(n_dev, -1), axis, 0, 0,
                              tiled=True)
    # row d of `back` = this device's rows as answered by owner device d;
    # every probe row was routed to exactly one owner
    return jnp.any(back.reshape(n_dev, -1), axis=0)


def make_routed_row_lookup(mesh, specs: Mapping, view_dims: Sequence[str],
                           n_parts: int, axis: str = "data"):
    """Standalone jitted routed row lookup (the fused query pipeline wraps
    :func:`_routed_lookup_body` itself; this factory serves benchmarks and
    ad-hoc probes). Returns ``f(columns, valid, t_hi, t_lo, keep) ->
    matched`` with rows sharded over ``axis`` and the (n_parts, C) view
    state sharded per partition. Row count must divide the axis size (the
    engine pads)."""
    import functools

    from repro.core.cem import make_codec

    vspecs = {d: specs[d] for d in view_dims}
    codec = make_codec(vspecs)
    n_dev = int(mesh.shape[axis])
    if n_parts % n_dev != 0:
        raise ValueError(f"n_parts={n_parts} must be a multiple of the "
                         f"data-axis size {n_dev}")
    body = functools.partial(_routed_lookup_body, codec=codec, specs=vspecs,
                             n_parts=n_parts, n_dev=n_dev, axis=axis)
    part = P(axis, None)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis), part, part, part),
                       out_specs=P(axis),
                       check_vma=False)
    from repro.launch.trace import counted_jit
    return counted_jit(fn, label="query")


# ============================= ring k-NN ====================================
def make_ring_knn(mesh, k: int, axis: str = "data"):
    """Returns jitted f(Q, C, c_valid) -> (dist, idx): for each query row,
    the k nearest controls ANYWHERE on the mesh. Q, C row-sharded over
    `axis`; outputs sharded like Q; idx are global control row ids."""

    def shard_body(Q, C, cv):
        n_dev = jax.lax.psum(1, axis)
        me = jax.lax.axis_index(axis)
        nc_local = C.shape[0]
        qn = jnp.sum(Q * Q, axis=1, keepdims=True)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

        def fold(carry, hop):
            run_d, run_i, Cb, cvb = carry
            owner = (me - hop) % n_dev          # whose shard we hold now
            cn = jnp.sum(Cb * Cb, axis=1)[None, :]
            d2 = jnp.maximum(qn + cn - 2.0 * (Q @ Cb.T), 0.0)
            d2 = jnp.where(cvb[None, :].astype(bool), d2, BIG)
            base = owner * nc_local
            idx = base + jnp.arange(nc_local, dtype=jnp.int32)[None, :]
            idx = jnp.broadcast_to(idx, d2.shape)
            bk = min(k, nc_local)
            nd, np_ = jax.lax.top_k(-d2, bk)
            ni = jnp.take_along_axis(idx, np_, axis=1)
            run_d, run_i = _topk_merge(run_d, run_i, -nd, ni, k)
            # pass the control shard along the ring
            Cb = jax.lax.ppermute(Cb, axis, perm)
            cvb = jax.lax.ppermute(cvb, axis, perm)
            return (run_d, run_i, Cb, cvb), None

        run_d = jnp.full((Q.shape[0], k), BIG, jnp.float32)
        run_i = jnp.full((Q.shape[0], k), -1, jnp.int32)
        (run_d, run_i, _, _), _ = jax.lax.scan(
            fold, (run_d, run_i, C, cv.astype(jnp.int32)),
            jnp.arange(n_dev))
        return jnp.sqrt(run_d), run_i

    fn = jax.shard_map(shard_body, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=(P(axis), P(axis)),
                       check_vma=False)
    from repro.launch.trace import counted_jit
    return counted_jit(fn)


# ===================== distributed propensity (Newton) ======================
def make_distributed_newton(mesh, n_iter: int = 32, ridge: float = 1e-4,
                            axis: str = "data"):
    """Batch-sharded logistic regression: per-device fused grad/Hessian
    partials (the logistic_grad kernel's math) + psum — exact Newton."""

    def shard_body(X, t, m):
        d = X.shape[1]

        def step(w, _):
            logits = X @ w
            p = jax.nn.sigmoid(logits)
            r = m * (p - t)
            g = X.T @ r
            s = m * p * (1.0 - p)
            H = (X * s[:, None]).T @ X
            g = jax.lax.psum(g, axis) + ridge * w
            H = jax.lax.psum(H, axis) + ridge * jnp.eye(d)
            return w - jnp.linalg.solve(H, g), None

        w0 = jnp.zeros((X.shape[1],), jnp.float32)
        w, _ = jax.lax.scan(step, w0, None, length=n_iter)
        return w

    fn = jax.shard_map(shard_body, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=P(),
                       check_vma=False)
    from repro.launch.trace import counted_jit
    return counted_jit(fn)
