"""Single-dispatch device-resident ingest: the whole delta pipeline of the
online engines as ONE compiled program per batch.

The PR 3 hot path still issued a Python loop of XLA calls per ingest — a
delta-build dispatch, a planner dispatch, per-view touch stamps — and fell
back to the HOST for growth merges and eviction compaction. ZaliQL's core
argument (PAPER.md §optimizations) is that the maintenance loop must live
inside the engine so no per-operation round trip leaves the data plane;
this module is that move for the jax port. One compiled program — a plain
jit on one device, a single ``shard_map`` over the data axis on a mesh —
takes the raw batch plus every view's state and internally does

  coarsen -> pack -> group (delta stat table)
  -> rollup per view -> route to owner partitions (all-to-all on a mesh)
  -> per-view merge:  lax.cond( every delta key already materialized,
         scatter-merge fast path,
         concat + re-sort grow path at the current capacity )
  -> incremental overlap flip -> touch stamp -> streaming-moments update
  -> verdict scalars (ok / grew / overflow / neg_min / cache predicate)

with BUFFER DONATION on every cuboid / keep / touch / reservoir array, so
state updates in place instead of copy-merge-copy. The host fetches one
fused ``device_get`` of the verdicts and commits by reference swap — the
steady-state ingest is exactly one compiled dispatch
(``repro.launch.trace`` counts them; ``tests/test_online_fused.py``
asserts the invariant). On a mesh, EVERYTHING (including the merges) runs
inside the one shard_map body: the only cross-device traffic is the
routing all-to-all / gathering all-gather of the tiny delta tables plus
scalar verdict reductions — the merge compute itself is per-device local
code, never GSPMD-partitioned small ops.

Growth is device-resident too: the re-sort branch merges at the CURRENT
capacity and reports ``grew`` when the merged group count would not fit;
the engine then pads the (pass-through, unmodified) state and re-dispatches
the same program compiled at the doubled capacity — a recompile keyed on
``(capacity, n_parts)``, so a stream that stops growing stops
recompiling. Delta-capacity overflow (more distinct groups in one batch
than the delta table holds) is handled the same way: the state passes
through, the engine doubles the delta capacity and re-dispatches.

Programs are cached at module level (``functools.lru_cache``) keyed on the
full schema + capacity signature, so every engine with the same shapes
shares one compilation.

QUERIES get the same treatment: :func:`get_fused_query_batch` answers
an uncached ``ate()`` (a one-spec wave) or a whole ``ate_batch`` wave with
ONE compiled dispatch straight on the raw (replicated or partitioned)
view state — subpopulation filter + keep mask from spec rows that are
data, in-program canonical key-sort, capacity-invariant canonical
reductions — and :func:`get_fused_rowlookup` answers ``matched_rows``
with one dispatch (routed all-to-all probe on a partitioned mesh). Query
programs take state BY REFERENCE (never donated) and return only scalars
or a per-row mask; the host fetches once and caches.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import cube as cube_mod
from repro.core import groupby
from repro.core.ate import estimate_ate_from_stats
from repro.core.cem import overlap_keep, update_overlap
from repro.core.keys import INVALID_HI, INVALID_LO
from repro.core.propensity import _stream_retract, _stream_update
from repro.kernels.segment_stats import canonical_sum
from repro.launch.trace import counted_jit, hot_path, stage

#: contract-lint scoping (tools/contract_check.py): this module is
#: engine-owned — dispatch/donation rules ZQL001-ZQL006 apply.
__engine_owned__ = True

BASE_VIEW = "__base__"


def query_stat_names(treatment: str) -> Tuple[str, ...]:
    """The stat columns one treatment's causal query consumes."""
    return ("one", "y", "yy", f"t_{treatment}", f"yt_{treatment}",
            f"yyt_{treatment}")

# renormalize int32 last-touch stamps when the ingest counter approaches
# the int32 ceiling (see OnlineEngine._renorm_touch). The shift is at
# least (counter - TOUCH_CLAMP_AGE): stamps older than that clamp to 0
# ("at least this old" — exact for every ttl < TOUCH_CLAMP_AGE,
# conservative beyond), which guarantees each renormalization buys
# ~TOUCH_CLAMP_AGE further ingests even when a cold live group pins the
# minimum stamp.
TOUCH_RENORM_LIMIT = (1 << 31) - (1 << 16)
TOUCH_CLAMP_AGE = 1 << 30


# ------------------------------------------------------------ touch stamps
@hot_path
def stamp_touch(touch: jnp.ndarray, pos: jnp.ndarray, dvalid: jnp.ndarray,
                counter) -> jnp.ndarray:
    """Record the current ingest counter at the touched group slots.
    Invalid delta rows are routed out of bounds and dropped, so a clipped
    lookup position can never stamp an unrelated live group."""
    upd = jnp.where(dvalid, pos, touch.shape[0])
    return touch.at[upd].set(jnp.int32(counter), mode="drop")


@hot_path
def remap_touch(old_hi, old_lo, old_gv, new_hi, new_lo,
                touch: jnp.ndarray) -> jnp.ndarray:
    """Carry last-touch stamps across a layout-changing (re-sort) merge."""
    pos, found = groupby.lookup_rows_in_table(old_hi, old_lo, new_hi, new_lo)
    upd = jnp.where(old_gv & found, pos, new_hi.shape[0])
    return jnp.zeros((new_hi.shape[0],), touch.dtype).at[upd].set(
        touch, mode="drop")


# ----------------------------------------------------------- merge kernels
@hot_path
def _merge_one_view(tname, st, d_hi, d_lo, d_stats, d_gv, counter,
                    use_pallas: bool):
    """One view's merge as a device-side branch: scatter fast path when
    every delta key is already materialized, concat + re-sort grow path at
    the CURRENT capacity otherwise (``grew`` reports a would-not-fit).

    ``st`` is the view's state dict; ``tname`` is None for the base view
    (which carries no overlap mask). Returns (new_st, verdicts)."""
    cap = st["hi"].shape[0]
    with stage("probe"):
        pos, found = groupby.lookup_rows_in_table(d_hi, d_lo, st["hi"],
                                                  st["lo"])
        ok = jnp.all(found | ~d_gv)
    has_keep = st.get("keep") is not None

    # each branch under the stage of its merge, so what the branch adds
    # around its inner stages (copies of its outputs) is that stage's
    @stage("merge_fast")
    def fast(_):
        mstats = cube_mod.scatter_merge_stats(st["stats"], pos, d_stats,
                                              use_pallas=use_pallas)
        keep = None
        if has_keep:
            with stage("overlap"):
                nt = mstats[f"t_{tname}"]
                keep = update_overlap(st["keep"], st["gv"], nt,
                                      mstats["one"] - nt, pos)
        touch = stamp_touch(st["touch"], pos, d_gv, counter)
        return (st["hi"], st["lo"], mstats, st["gv"], keep, touch, pos,
                jnp.int32(0))

    @stage("resort")
    def slow(_):
        cat_hi = jnp.concatenate([st["hi"], d_hi])
        cat_lo = jnp.concatenate([st["lo"], d_lo])
        g = groupby.group_by_key(cat_hi, cat_lo)
        sums = groupby.segment_sums(
            g, {k: jnp.concatenate([st["stats"][k], d_stats[k]])
                for k in st["stats"]})
        n_merged = g.n_groups
        nhi, nlo = g.group_hi[:cap], g.group_lo[:cap]
        ngv = g.group_valid[:cap]
        nstats = {k: v[:cap] for k, v in sums.items()}
        with stage("relocate"):
            pos2, _ = groupby.lookup_rows_in_table(d_hi, d_lo, nhi, nlo)
        keep = None
        if has_keep:
            with stage("overlap"):
                nt = nstats[f"t_{tname}"]
                keep = overlap_keep(ngv, nt, nstats["one"] - nt)
        with stage("touch_remap"):
            moved = remap_touch(st["hi"], st["lo"], st["gv"], nhi, nlo,
                                st["touch"])
        with stage("relocate"):
            touch = stamp_touch(moved, pos2, d_gv, counter)
        return nhi, nlo, nstats, ngv, keep, touch, pos2, n_merged

    with stage("branch"):
        hi, lo, stats, gv, keep, touch, pos_out, n_merged = jax.lax.cond(
            ok, fast, slow, None)
    new_st = dict(hi=hi, lo=lo, stats=stats, gv=gv, touch=touch)
    if has_keep:
        new_st["keep"] = keep
    with stage("gate"):
        grew = n_merged > cap
    return new_st, dict(ok=ok, grew=grew, n_merged=n_merged,
                        pos=pos_out, merged_stats=stats)


@hot_path
def _merge_one_view_parts(tname, st, d_hi, d_lo, d_stats, d_gv, counter,
                          use_pallas: bool, axis=None):
    """Partitioned analogue of :func:`_merge_one_view`: state is (P, C)
    (the LOCAL (k, C) slice inside a shard_map body), routed deltas
    (P, B); the fast/slow decision is GLOBAL per view — one scalar over
    all partitions on all devices (``axis`` names the mesh axis for the
    cross-device reduction), matching the PR 3 planner verdicts — so the
    cond lifts outside the per-partition vmap and the untaken branch never
    executes."""
    cap = st["hi"].shape[1]
    with stage("probe"):
        pos, found = jax.vmap(groupby.lookup_rows_in_table)(
            d_hi, d_lo, st["hi"], st["lo"])
        ok = jnp.all(found | ~d_gv)
        if axis is not None:
            ok = jax.lax.pmin(ok.astype(jnp.int32), axis) > 0
    has_keep = st.get("keep") is not None

    # each branch under the stage of its merge (see _merge_one_view)
    @stage("merge_fast")
    def fast(_):
        mstats = cube_mod.scatter_merge_stats_parts(
            st["stats"], pos, d_stats, use_pallas=use_pallas)
        keep = None
        if has_keep:
            with stage("overlap"):
                nt = mstats[f"t_{tname}"]
                keep = jax.vmap(update_overlap)(st["keep"], st["gv"], nt,
                                                mstats["one"] - nt, pos)
        touch = jax.vmap(stamp_touch, in_axes=(0, 0, 0, None))(
            st["touch"], pos, d_gv, counter)
        return (st["hi"], st["lo"], mstats, st["gv"], keep, touch, pos,
                jnp.int32(0))

    @stage("resort")
    def slow(_):
        def one(thi, tlo, tstats, tgv, dhi, dlo, dstats, dgv, tch):
            cat_hi = jnp.concatenate([thi, dhi])
            cat_lo = jnp.concatenate([tlo, dlo])
            g = groupby.group_by_key(cat_hi, cat_lo)
            sums = groupby.segment_sums(
                g, {k: jnp.concatenate([tstats[k], dstats[k]])
                    for k in tstats})
            nhi, nlo = g.group_hi[:cap], g.group_lo[:cap]
            nstats = {k: v[:cap] for k, v in sums.items()}
            with stage("relocate"):
                p2, _ = groupby.lookup_rows_in_table(dhi, dlo, nhi, nlo)
            with stage("touch_remap"):
                moved = remap_touch(thi, tlo, tgv, nhi, nlo, tch)
            with stage("relocate"):
                tch2 = stamp_touch(moved, p2, dgv, counter)
            return (nhi, nlo, nstats, g.group_valid[:cap], tch2, p2,
                    g.n_groups)

        nhi, nlo, nstats, ngv, touch, pos2, nm = jax.vmap(one)(
            st["hi"], st["lo"], st["stats"], st["gv"], d_hi, d_lo, d_stats,
            d_gv, st["touch"])
        keep = None
        if has_keep:
            with stage("overlap"):
                nt = nstats[f"t_{tname}"]
                keep = jax.vmap(overlap_keep)(ngv, nt, nstats["one"] - nt)
        return nhi, nlo, nstats, ngv, keep, touch, pos2, jnp.max(nm)

    with stage("branch"):
        hi, lo, stats, gv, keep, touch, pos_out, n_merged = jax.lax.cond(
            ok, fast, slow, None)
    with stage("gate"):
        if axis is not None:
            # cond branches hold no collectives; globalize the verdicts
            # after
            n_merged = jax.lax.pmax(n_merged, axis)
        grew = n_merged > cap
    new_st = dict(hi=hi, lo=lo, stats=stats, gv=gv, touch=touch)
    if has_keep:
        new_st["keep"] = keep
    return new_st, dict(ok=ok, grew=grew, n_merged=n_merged,
                        pos=pos_out, merged_stats=stats)


@hot_path
def _neg_min(stats: Dict[str, jnp.ndarray], tnames, axis=None):
    """Minimum over every count column — the retraction-negativity probe."""
    cols = [stats["one"]] + [stats[f"t_{t}"] for t in tnames]
    m = jnp.min(jnp.stack([jnp.min(c) for c in cols]))
    return m if axis is None else jax.lax.pmin(m, axis)


@hot_path
def _gate(commit, new_tree, old_tree):
    """Select committed-vs-pass-through state leaf-wise. XLA still aliases
    the donated input buffers; the untaken value only costs the select."""
    return jax.tree.map(lambda n, o: jnp.where(commit, n, o),
                        new_tree, old_tree)


@hot_path
def _stream_step(stream, stream_names, columns, valid, retract, seed,
                 n_batches):
    """Streaming-propensity update (moments + reservoir) inside the fused
    program — the last separate dispatch of the PR 3 ingest path.

    Always runs over the FULL, UNPADDED batch (in the mesh programs it
    therefore sits OUTSIDE the shard_map body, gated by the replicated
    commit scalar): the stream state is replicated, and the reservoir's
    uniform priorities depend on the draw SHAPE, so only the original
    batch length reproduces the host path bit for bit."""
    cols = {c: columns[c] for c in stream_names}
    if retract:
        res, pri, n, sums, sumsqs = _stream_retract(
            stream_names, stream["res"], stream["pri"], stream["n"],
            stream["sums"], stream["sumsqs"], cols, valid)
    else:
        key = jax.random.fold_in(jax.random.PRNGKey(seed), n_batches)
        res, pri, n, sums, sumsqs = _stream_update(
            stream_names, stream["res"], stream["pri"], stream["n"],
            stream["sums"], stream["sumsqs"], cols, valid, key)
    return dict(res=res, pri=pri, n=n, sums=sums, sumsqs=sumsqs)


def _view_scope(name: str):
    """``jax.named_scope`` around one view's stages of an ingest program."""
    return jax.named_scope(f"view_{name}")


def pad_tail(columns, valid, pad: int):
    """Append ``pad`` invalid rows to a columnar batch — THE one
    definition of row padding (mesh divisibility, power-of-two batch
    buckets) shared by the engines and the fused program bodies, so
    padding semantics can never diverge between call sites."""
    if pad:
        columns = {k: jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
                   for k, v in columns.items()}
        valid = jnp.pad(valid, (0, pad))
    return columns, valid


def _pad_batch(columns, valid, ndev: int):
    return pad_tail(columns, valid, (-valid.shape[0]) % ndev)


# ===================== replicated single-dispatch ingest ====================
@functools.lru_cache(maxsize=128)
def get_fused_ingest(codec, specs_items, tnames: Tuple[str, ...],
                     view_dims: Tuple, outcome: str, caps: Tuple,
                     delta_cap: int, mesh, mesh_axis: str, use_pallas: bool,
                     retract: bool, stream_names: Tuple[str, ...],
                     seed: int, donate: bool = True):
    """One-dispatch ingest program for the REPLICATED engine.

    view_dims: ((name, dims), ...) with the base view first; caps:
    ((name, capacity), ...) — part of the cache key, so capacity growth
    recompiles and a stable stream reuses one executable. stream_names=()
    disables the reservoir section. The state argument is DONATED unless
    ``donate=False`` — the MVCC double-buffer rule: the synchronous path
    and chained in-flight hops consume their input in place, but the FIRST
    hop off a committed snapshot must leave the committed buffers alive
    (they keep serving queries and anchor rollback-and-replay on a failed
    commit; see ``OnlineEngine.commit``). On a mesh the whole pipeline —
    sharded build AND merges — is one shard_map body (merges replicated
    per-device local code; no GSPMD-sharded small ops)."""
    del caps  # cache key only: capacities are read off the state shapes
    specs = dict(specs_items)
    ndev = 1 if mesh is None else int(mesh.shape[mesh_axis])
    rollups = {name: dims for name, dims in view_dims if name != BASE_VIEW}

    def local_build(columns, valid):
        with stage("build"):
            hi, lo, sums, gv, n_groups = cube_mod.delta_build_body(
                columns, valid, codec=codec, specs=specs, treatments=tnames,
                outcome=outcome)
        return hi, lo, sums, gv, n_groups, jnp.asarray(False)

    def merge_and_gate(delta, views, counter):
        """Everything after the delta build except the stream update —
        pure per-device local compute, shared verbatim by the 1-device and
        shard_map paths."""
        with stage("build"):
            hi, lo, stats, gv, n_full, overflow = delta
            dcap = delta_cap
            d_hi, d_lo, d_gv = hi[:dcap], lo[:dcap], gv[:dcap]
            d_stats = {k: v[:dcap] for k, v in stats.items()}
            overflow = overflow | (n_full > dcap)
            if retract:
                d_stats = {k: -v for k, v in d_stats.items()}
        new_views, verdicts = {}, {}
        for name in (BASE_VIEW,) + tnames:
            with _view_scope(name):
                if name == BASE_VIEW:
                    v_hi, v_lo, v_stats, v_gv = d_hi, d_lo, d_stats, d_gv
                else:
                    with stage("rollup"):
                        roll = cube_mod._rollup_fn(codec, rollups[name])
                        v_hi, v_lo, v_stats, v_gv = roll(d_hi, d_lo, d_gv,
                                                         d_stats)
                tname = None if name == BASE_VIEW else name
                new_views[name], verdicts[name] = _merge_one_view(
                    tname, views[name], v_hi, v_lo, v_stats, v_gv,
                    counter, use_pallas)
        with stage("gate"):
            all_ok = functools.reduce(
                jnp.logical_and, [v["ok"] for v in verdicts.values()])
            any_grew = functools.reduce(
                jnp.logical_or, [v["grew"] for v in verdicts.values()])
            neg = _neg_min(verdicts[BASE_VIEW]["merged_stats"], tnames)
            commit = ~overflow & ~any_grew
            if retract:
                commit = commit & all_ok & (neg >= -0.5)
            out = dict(
                overflow=overflow, n_full=n_full, commit=commit,
                neg_min=neg,
                ok={k: v["ok"] for k, v in verdicts.items()},
                grew={k: v["grew"] for k, v in verdicts.items()},
                n_merged={k: v["n_merged"] for k, v in verdicts.items()},
                n_delta=jnp.sum(d_gv.astype(jnp.int32)),
                gv=d_gv,
                buckets={d: codec.extract(d_hi, d_lo, d)
                         for d in codec.names})
            return _gate(commit, new_views, views), out

    def finish(new_views, out, state, columns, valid, n_batches):
        """Attach the stream update (full UNPADDED batch — reservoir
        priorities depend on the draw shape) gated by the commit scalar."""
        new_state = dict(views=new_views)
        if stream_names:
            with stage("stream"):
                upd = _stream_step(state["stream"], stream_names, columns,
                                   valid, retract, seed, n_batches)
                new_state["stream"] = _gate(out["commit"], upd,
                                            state["stream"])
        return new_state, out

    if ndev > 1:
        from repro.core.distributed import _sharded_delta_body
        build = functools.partial(_sharded_delta_body, codec=codec,
                                  specs=specs, treatments=tnames,
                                  outcome=outcome, capacity=delta_cap,
                                  axis=mesh_axis)

        def body(columns, valid, views, counter):
            with stage("build"):
                delta = build(columns, valid)
            return merge_and_gate(delta, views, counter)

        def ingest_program(columns, valid, state, counter, n_batches):
            with stage("build"):
                pcols, pvalid = _pad_batch(columns, valid, ndev)
            new_views, out = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(mesh_axis), P(mesh_axis), P(), P()),
                out_specs=(P(), P()),
                check_vma=False)(pcols, pvalid, state["views"], counter)
            return finish(new_views, out, state, columns, valid, n_batches)
    else:
        def ingest_program(columns, valid, state, counter, n_batches):
            new_views, out = merge_and_gate(local_build(columns, valid),
                                            state["views"], counter)
            return finish(new_views, out, state, columns, valid, n_batches)

    return counted_jit(ingest_program, label="ingest",
                       donate_argnums=(2,) if donate else ())


# ===================== partitioned single-dispatch ingest ===================
@functools.lru_cache(maxsize=128)
def get_fused_ingest_parts(codec, specs_items, tnames: Tuple[str, ...],
                           view_dims: Tuple, outcome: str, caps: Tuple,
                           delta_cap: int, n_parts: int, mesh,
                           mesh_axis: str, use_pallas: bool, retract: bool,
                           stream_names: Tuple[str, ...], seed: int,
                           donate: bool = True):
    """One-dispatch ingest program for the PARTITIONED engine: routed
    delta build (all-to-all on a mesh, in-program regroup off one) composed
    with the per-partition merges, overlap flips, touch stamps and verdict
    scalars — the whole maintenance loop of one batch in one executable,
    with the (P, C) state donated in place (``donate=False`` keeps the
    input alive — the MVCC first-hop rule, see :func:`get_fused_ingest`). ``n_parts`` may be any multiple
    of the mesh data-axis size: each device owns ``k = n_parts / N``
    contiguous key ranges (k-partitions-per-device). On a mesh the whole
    pipeline is ONE shard_map body: state enters as the local (k, C)
    slice, merges are partition-local, and only the delta routing
    (all-to-all) plus scalar verdict reductions cross devices."""
    del caps  # cache key only
    specs = dict(specs_items)
    ndev = 1 if mesh is None else int(mesh.shape[mesh_axis])
    view_items = tuple(view_dims)

    def merge_and_gate(deltas, n_full, overflow, views, counter, axis):
        new_views, verdicts = {}, {}
        for name, _ in view_items:
            with _view_scope(name):
                d_hi, d_lo, d_stats, d_gv = deltas[name]
                if retract:
                    with stage("build"):
                        d_stats = {k: -v for k, v in d_stats.items()}
                    deltas[name] = (d_hi, d_lo, d_stats, d_gv)
                tname = None if name == BASE_VIEW else name
                new_views[name], verdicts[name] = _merge_one_view_parts(
                    tname, views[name], d_hi, d_lo, d_stats, d_gv,
                    counter, use_pallas, axis=axis)
        with stage("gate"):
            all_ok = functools.reduce(
                jnp.logical_and, [v["ok"] for v in verdicts.values()])
            any_grew = functools.reduce(
                jnp.logical_or, [v["grew"] for v in verdicts.values()])
            neg = _neg_min(verdicts[BASE_VIEW]["merged_stats"], tnames,
                           axis=axis)
            commit = ~overflow & ~any_grew
            if retract:
                commit = commit & all_ok & (neg >= -0.5)
            b_gv = deltas[BASE_VIEW][3]
            n_delta = jnp.sum(b_gv.astype(jnp.int32))
            if axis is not None:
                n_delta = jax.lax.psum(n_delta, axis)
            out = dict(
                overflow=overflow, n_full=n_full, commit=commit,
                neg_min=neg,
                ok={k: v["ok"] for k, v in verdicts.items()},
                grew={k: v["grew"] for k, v in verdicts.items()},
                n_merged={k: v["n_merged"] for k, v in verdicts.items()},
                n_delta=n_delta,
                gv=b_gv,
                buckets={d: codec.extract(deltas[BASE_VIEW][0],
                                          deltas[BASE_VIEW][1], d)
                         for d in codec.names})
            return _gate(commit, new_views, views), out

    def finish(new_views, out, state, columns, valid, n_batches):
        new_state = dict(views=new_views)
        if stream_names:
            with stage("stream"):
                upd = _stream_step(state["stream"], stream_names, columns,
                                   valid, retract, seed, n_batches)
                new_state["stream"] = _gate(out["commit"], upd,
                                            state["stream"])
        return new_state, out

    if ndev > 1:
        from repro.core.distributed import _routed_delta_body
        build = functools.partial(
            _routed_delta_body, codec=codec, specs=specs,
            treatments=tnames, outcome=outcome, capacity=delta_cap,
            view_items=view_items, n_parts=n_parts, n_dev=ndev,
            axis=mesh_axis)

        def body(columns, valid, views, counter):
            with stage("build"):
                deltas, n_full, overflow = build(columns, valid)
            return merge_and_gate(deltas, n_full, overflow, views, counter,
                                  mesh_axis)

        part = P(mesh_axis, None)
        out_spec = dict(overflow=P(), n_full=P(), commit=P(), neg_min=P(),
                        ok=P(), grew=P(), n_merged=P(), n_delta=P(),
                        gv=part, buckets=part)

        def ingest_program(columns, valid, state, counter, n_batches):
            with stage("build"):
                pcols, pvalid = _pad_batch(columns, valid, ndev)
            new_views, out = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(mesh_axis), P(mesh_axis), part, P()),
                out_specs=(part, out_spec),
                check_vma=False)(pcols, pvalid, state["views"], counter)
            return finish(new_views, out, state, columns, valid, n_batches)
    else:
        def single_build(columns, valid):
            with stage("build"):
                hi, lo, sums, gv, n_groups = cube_mod.delta_build_body(
                    columns, valid, codec=codec, specs=specs,
                    treatments=tnames, outcome=outcome)
                dcap = delta_cap
                b_hi, b_lo, b_gv = hi[:dcap], lo[:dcap], gv[:dcap]
                b_stats = {k: v[:dcap] for k, v in sums.items()}
                overflow = n_groups > dcap
            deltas = {}
            for name, dims in view_items:
                with _view_scope(name), stage("rollup"):
                    if name == BASE_VIEW:
                        v = (b_hi, b_lo, b_stats, b_gv)
                    else:
                        roll = cube_mod._rollup_fn(codec, dims)
                        v = roll(b_hi, b_lo, b_gv, b_stats)
                    deltas[name] = cube_mod.route_delta(*v, n_parts)
            return deltas, n_groups, overflow

        def ingest_program(columns, valid, state, counter, n_batches):
            deltas, n_full, overflow = single_build(columns, valid)
            new_views, out = merge_and_gate(deltas, n_full, overflow,
                                            state["views"], counter, None)
            return finish(new_views, out, state, columns, valid, n_batches)

    return counted_jit(ingest_program, label="ingest",
                       donate_argnums=(2,) if donate else ())


# ===================== device-resident query pipeline =======================
@hot_path
def _query_mask(hi, lo, gv, keep, codec, subpop):
    """Subpopulation filter + overlap keep as ONE elementwise mask — the
    per-partition (per-device-local, 1/N) stage of a query. ``subpop`` is
    the frozen ((dim, (bucket, ...)), ...) predicate, static per program."""
    m = gv & keep
    if subpop:
        for dim, allowed in subpop:
            vals = codec.extract(hi, lo, dim)
            ok = jnp.zeros_like(m)
            for b in allowed:
                ok = ok | (vals == b)
            m = m & ok
    return m


# role-named stat columns the canonical estimator body consumes, in the
# order :func:`query_stat_names` yields the treatment-specific names
QUERY_ROLES = ("one", "y", "yy", "t", "yt", "yyt")


@hot_path
def _estimate_from_roles(hi, lo, stats, m):
    """Canonical estimate over the masked groups: re-sort the surviving
    keys into the canonical (globally key-sorted, valid-prefix) order —
    keys are unique across partitions, so the segment sums are exact
    gathers — then reduce with the capacity-invariant canonical sum
    (:func:`repro.kernels.segment_stats.canonical_sum`). The result is a
    bitwise-deterministic function of the surviving group stats alone:
    identical for replicated/partitioned layouts, any partition count, any
    capacity history, and identical to the ``assemble`` baseline path.
    ``stats`` carries the ROLE-named columns (:data:`QUERY_ROLES`); this
    one body is shared verbatim by the single-spec and batched query
    programs, which is what makes their answers bit-identical."""
    hi = hi.reshape(-1)
    lo = lo.reshape(-1)
    m = m.reshape(-1)
    chi = jnp.where(m, hi, INVALID_HI)
    clo = jnp.where(m, lo, INVALID_LO)
    g = groupby.group_by_key(chi, clo)
    sums = groupby.segment_sums(
        g, {k: jnp.where(m, v.reshape(-1), 0.0) for k, v in stats.items()})
    keep = g.group_valid
    nt = sums["t"]
    nc = sums["one"] - nt
    yt = sums["yt"]
    yc = sums["y"] - yt
    yyt = sums["yyt"]
    yyc = sums["yy"] - yyt
    est = estimate_ate_from_stats(keep, nt, nc, yt, yc, sum_yy_t=yyt,
                                  sum_yy_c=yyc, sum_fn=canonical_sum)
    return dict(ate=est.ate, att=est.att,
                n_matched_treated=est.n_matched_treated,
                n_matched_control=est.n_matched_control,
                n_groups=est.n_groups, variance=est.variance)


@hot_path
def _estimate_from_masked(hi, lo, stats, m, treatment):
    """Treatment-named front of :func:`_estimate_from_roles`: map the
    view's stat columns onto the estimator roles and estimate."""
    roles = dict(zip(QUERY_ROLES,
                     (stats[k] for k in query_stat_names(treatment))))
    return _estimate_from_roles(hi, lo, roles, m)


@hot_path
def estimate_view_body(hi, lo, stats, gv, keep, *, codec, treatment,
                       subpop):
    """Whole causal query as pure traced compute: mask then canonical
    estimate. Shared verbatim by the fused one-dispatch query program and
    the ``assemble`` baseline (which feeds it the reassembled view) — one
    definition of the estimator across every query pipeline."""
    m = _query_mask(hi, lo, gv, keep, codec, subpop)
    return _estimate_from_masked(hi, lo, stats, m, treatment)


@functools.lru_cache(maxsize=512)
def get_fused_query(codec, treatment: str, subpop):
    """One-dispatch causal query program with the subpopulation predicate
    in its trace: ``f(hi, lo, stats, gv, keep) -> {ate, att,
    n_matched_*, n_groups, variance}`` over one replicated ``(C,)`` view
    (the ``query_pipeline="assemble"`` baseline feeds it the reassembled
    canonical view). ``subpop`` is the frozen predicate and part of the
    cache key, so each distinct subpopulation compiles its own program;
    the default ``ate()`` path is the batched program below at B=1, whose
    spec is data."""
    def query_program(hi, lo, stats, gv, keep):
        return estimate_view_body(hi, lo, stats, gv, keep, codec=codec,
                                  treatment=treatment, subpop=subpop)

    return counted_jit(query_program, label="query")


# ===================== batched query: the spec table is DATA ================
#
# A single-spec query program bakes the subpopulation predicate into the
# trace (it is part of get_fused_query's cache key), so B heterogeneous
# queries cost B dispatches. The batched variant moves the WHOLE query spec
# — view choice, estimand, subpopulation predicate — into a fixed-width
# device-resident uint32 row per query:
#
#   word 0            view id (index into the engine's sorted treatments)
#   word 1            estimand selector (0 = ATE, 1 = ATT)
#   words 2..2+W-1    per-dim allowed-bucket BITMASKS in the engine's
#                     base-dim layout: dim d with cardinality c owns
#                     ceil(c/32) words; bit b set <=> bucket b passes.
#                     An unrestricted dim is all-ones.
#
# The per-group predicate test becomes one gather + bit-test per dim —
# exactly the same boolean mask _query_mask builds by unrolled equality,
# so the downstream canonical estimate (shared `_estimate_from_roles`
# body, capacity-invariant canonical_sum reduce) returns bit-identical
# answers, while the program itself is cached on SHAPES ONLY (view
# schema, word layout, pow2 spec-count bucket) — any B specs with any
# predicates run through ONE compiled dispatch with no retrace.

SPEC_META_WORDS = 2    # [view id, estimand] prefix of an encoded spec row
ESTIMAND_IDS = {"ate": 0, "att": 1}


def spec_word_layout(cards: Tuple[Tuple[str, int], ...]
                     ) -> Tuple[Dict[str, int], int]:
    """Word layout of the predicate part of a spec row. ``cards`` is the
    engine's base-dim schema as sorted ``(dim, cardinality)`` pairs —
    cardinalities are static (``CoarsenSpec.n_buckets``), so every spec of
    an engine encodes at the same fixed width. Returns (word offset per
    dim, total predicate words W)."""
    offs, pos = {}, 0
    for dim, card in cards:
        offs[dim] = pos
        pos += (int(card) + 31) // 32
    return offs, pos


def encode_query_spec(cards: Tuple[Tuple[str, int], ...], view_id: int,
                      estimand_id: int, subpop) -> np.ndarray:
    """Host-side encoding of ONE query spec into its fixed-width uint32
    row. ``subpop`` is the frozen ``((dim, (bucket, ...)), ...)`` predicate
    (or None). Raises on buckets outside a dim's cardinality — the same
    queries the static path would answer with an empty match."""
    offs, n_words = spec_word_layout(cards)
    row = np.zeros((SPEC_META_WORDS + n_words,), np.uint32)
    row[0] = np.uint32(view_id)
    row[1] = np.uint32(estimand_id)
    by_dim = dict(subpop or ())
    unknown = set(by_dim) - set(offs)
    if unknown:
        raise ValueError(f"subpopulation dims {sorted(unknown)} not in the "
                         f"engine schema {sorted(offs)}")
    for dim, card in cards:
        base = SPEC_META_WORDS + offs[dim]
        nw = (int(card) + 31) // 32
        if dim in by_dim:
            for b in by_dim[dim]:
                b = int(b)
                if not 0 <= b < card:
                    raise ValueError(f"bucket {b} out of range for dim "
                                     f"{dim!r} (cardinality {card})")
                row[base + (b >> 5)] |= np.uint32(1) << np.uint32(b & 31)
        else:
            row[base:base + nw] = np.uint32(0xFFFFFFFF)
    return row


@hot_path
def _words_mask(hi, lo, base_m, codec, words, cards, offsets):
    """Data-driven :func:`_query_mask`: evaluate one encoded predicate
    (the ``(W,)`` uint32 bitmask slice of a spec row) over one view's
    keys. Bit-for-bit the same boolean mask the static path builds: each
    dim extracts its bucket id and tests membership in the allowed-bucket
    bitmask (unrestricted dims are all-ones, a no-op AND). Dims absent
    from this view's codec are skipped — the engine validates host-side
    that a spec only restricts dims its view materializes."""
    m = base_m
    names = set(codec.names)
    for dim, _card in cards:
        if dim not in names:
            continue
        vals = codec.extract(hi, lo, dim)          # int32, < card for valid
        idx = jnp.clip(offsets[dim] + (vals >> 5), 0, words.shape[0] - 1)
        bit = (words[idx] >> (vals & 31).astype(jnp.uint32)) & jnp.uint32(1)
        m = m & (bit == jnp.uint32(1))
    return m


@hot_path
def _batched_query_body(view_schema, cards, offsets, view_states,
                        spec_rows):
    """B heterogeneous query specs over V materialized views as pure
    traced compute. Every view's state is flattened and zero/invalid-
    padded to one common length L, so per-spec state selection is a plain
    gather by view id; padding cannot perturb the answer because the
    canonical reduce is bitwise invariant to trailing invalid/zero tail
    (the same contract that makes capacity growth and partition count
    invisible — see ``canonical_sum``). Estimates run once per SPEC (not
    per spec x view): masks are evaluated per view (each view's codec is
    static), then each spec gathers its own view's mask row."""
    sizes = [int(np.prod(st[0].shape))  # zql: ok[ZQL002] static shapes
             for st in view_states]
    length = max(sizes)

    def padded(x, fill):
        x = x.reshape(-1)
        pad = length - x.shape[0]
        return jnp.pad(x, (0, pad), constant_values=fill) if pad else x

    words = spec_rows[:, SPEC_META_WORDS:]
    phi, plo, pstats, masks = [], [], [], []
    for (_, codec), (hi, lo, stats, gv, keep) in zip(view_schema,
                                                     view_states):
        bhi = padded(hi, INVALID_HI)
        blo = padded(lo, INVALID_LO)
        base_m = padded(gv & keep, False)
        pstats.append(tuple(padded(s, 0.0) for s in stats))
        masks.append(jax.vmap(
            lambda w, h=bhi, l=blo, bm=base_m, c=codec:
            _words_mask(h, l, bm, c, w, cards, offsets))(words))
        phi.append(bhi)
        plo.append(blo)
    phi = jnp.stack(phi)                       # (V, L)
    plo = jnp.stack(plo)
    pst = tuple(jnp.stack([pstats[v][r] for v in range(len(view_schema))])
                for r in range(len(QUERY_ROLES)))
    m_all = jnp.stack(masks)                   # (V, B, L)
    view_ids = spec_rows[:, 0].astype(jnp.int32)
    estimands = spec_rows[:, 1].astype(jnp.int32)
    m_sel = m_all[view_ids, jnp.arange(spec_rows.shape[0])]

    def one(vid, est_sel, m):
        stats = dict(zip(QUERY_ROLES, (s[vid] for s in pst)))
        out = _estimate_from_roles(phi[vid], plo[vid], stats, m)
        out["value"] = jnp.where(est_sel == 0, out["ate"], out["att"])
        return out

    return jax.vmap(one)(view_ids, estimands, m_sel)


@functools.lru_cache(maxsize=64)
def get_fused_query_batch(view_schema, cards, b_bucket: int, mesh,
                          mesh_axis: str, partitioned: bool):
    """ONE-dispatch batched causal query program:
    ``f(view_states, spec_rows) -> {ate, att, value, n_matched_*,
    n_groups, variance}`` with every output a ``(B,)`` array.

    ``view_schema`` is the engine's views as ``(treatment, codec)`` in
    view-id order; ``view_states`` a matching tuple of ``(hi, lo,
    role-ordered stats, group_valid, keep)``; ``spec_rows`` the ``(B,
    SPEC_META_WORDS + W)`` encoded spec table (:func:`encode_query_spec`).
    The cache key is shapes/schema ONLY — predicates arrive as data, so B
    heterogeneous specs (mixed views, estimands, subpopulations) share one
    compilation, and any batch inside the same pow2 ``b_bucket`` reuses
    the trace.

    On a mesh with partitioned ``(P, C)`` state the program is one
    ``shard_map`` body that all_gathers each view's raw partition tables
    ONCE (state-sized traffic, not B masked copies) and then runs the
    identical replicated batched estimate — the final reduce stays the
    canonical pairwise reduction, never a psum, so answers are
    bit-identical to the B=1 fused path on 1/2/4-device meshes."""
    offsets, _ = spec_word_layout(cards)
    ndev = 1 if mesh is None else int(mesh.shape[mesh_axis])

    if partitioned and ndev > 1:
        def sm_body(view_states, spec_rows):
            def g(x):
                return jax.lax.all_gather(x, mesh_axis, tiled=True)
            gathered = tuple(
                (g(hi), g(lo), tuple(g(s) for s in stats), g(gv), g(keep))
                for hi, lo, stats, gv, keep in view_states)
            return _batched_query_body(view_schema, cards, offsets,
                                       gathered, spec_rows)

        part = P(mesh_axis, None)
        state_spec = tuple(
            (part, part, (part,) * len(QUERY_ROLES), part, part)
            for _ in view_schema)

        def query_batch_program(view_states, spec_rows):
            return jax.shard_map(sm_body, mesh=mesh,
                                 in_specs=(state_spec, P()), out_specs=P(),
                                 check_vma=False)(view_states, spec_rows)
    else:
        def query_batch_program(view_states, spec_rows):
            return _batched_query_body(view_schema, cards, offsets,
                                       view_states, spec_rows)

    return counted_jit(query_batch_program, label="query")


@functools.lru_cache(maxsize=256)
def get_fused_rowlookup(codec, specs_items: Tuple, n_parts: int, mesh,
                        mesh_axis: str):
    """One-dispatch ``matched_rows`` program: ``f(columns, valid, t_hi,
    t_lo, keep) -> matched`` — coarsen + pack the probe rows, look each
    key up in the materialized view, and apply the overlap mask, all in
    one compiled program. ``n_parts == 0`` marks the replicated ``(C,)``
    layout (plain binary search in the broadcast table); ``n_parts > 0``
    the partitioned ``(P, C)`` one, where each probe row hashes to its
    owning partition and binary-searches ONLY that partition's table. On
    a mesh the partitioned variant is the ROUTED lookup
    (:func:`repro.core.distributed._routed_lookup_body`): probe keys hash
    to owner devices, cross with one all-to-all, answer with a local
    search, and route back — no device ever reassembles the view."""
    specs = dict(specs_items)
    ndev = 1 if mesh is None else int(mesh.shape[mesh_axis])

    if n_parts > 0 and ndev > 1:
        from repro.core.distributed import _routed_lookup_body
        body = functools.partial(_routed_lookup_body, codec=codec,
                                 specs=specs, n_parts=n_parts, n_dev=ndev,
                                 axis=mesh_axis)
        part = P(mesh_axis, None)

        def rowlookup_program(columns, valid, t_hi, t_lo, keep):
            n = valid.shape[0]
            pcols, pvalid = _pad_batch(columns, valid, ndev)
            matched = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(mesh_axis), P(mesh_axis), part, part, part),
                out_specs=P(mesh_axis),
                check_vma=False)(pcols, pvalid, t_hi, t_lo, keep)
            return matched[:n]
    else:
        from repro.core.coarsen import coarsen_columns

        def rowlookup_program(columns, valid, t_hi, t_lo, keep):
            buckets = coarsen_columns(columns, specs)
            hi, lo = codec.pack(buckets, valid)
            if n_parts == 0:
                pos, found = groupby.lookup_rows_in_table(hi, lo, t_hi,
                                                          t_lo)
                return valid & found & keep[pos]
            pid = cube_mod.partition_ids(hi, lo, n_parts)
            pos, found = groupby.lookup_rows_in_parts(hi, lo, pid, t_hi,
                                                      t_lo)
            return valid & found & keep[pid, pos]

    return counted_jit(rowlookup_program, label="query")


# ===================== device-resident eviction compaction ==================
@hot_path
def _compact_one(hi, lo, stats, gv, touch, keep_mask):
    """Capacity-preserving device compaction of one sorted stat table:
    dropped groups take the invalid-key marker, a stable re-sort pushes
    them to the tail, and stats/touch are carried by exact GATHER (keys are
    unique, so no float re-summation — surviving groups are bit-identical,
    in the same canonical key order the host compaction produced)."""
    new_gv = gv & keep_mask
    chi = jnp.where(new_gv, hi, INVALID_HI)
    clo = jnp.where(new_gv, lo, INVALID_LO)
    g = groupby.group_by_key(chi, clo)
    out_stats = {k: jnp.where(new_gv, v, 0.0)[g.perm]
                 for k, v in stats.items()}
    out_touch = jnp.where(g.group_valid, touch[g.perm], 0)
    return g.group_hi, g.group_lo, out_stats, g.group_valid, out_touch


@functools.lru_cache(maxsize=128)
def get_fused_evict(tnames: Tuple[str, ...], caps: Tuple, n_parts: int,
                    mesh, mesh_axis: str, has_stream: bool):
    """One-dispatch TTL eviction for every view at once: keep-mask from the
    touch stamps, per-partition device compaction (n_parts == 0 marks the
    replicated (C,) layout), overlap recompute, per-view evicted counts
    AND post-compaction live occupancy (max per partition — the input of
    the capacity-shrink pass) as the only fetched scalars. State is
    DONATED — eviction, like ingest, updates in place. On a mesh, runs as
    one shard_map body over the local partition slices (replicated state:
    local full copy). Closes ROADMAP open item "eviction compaction runs
    on the host per partition"."""
    del caps  # part of the cache key only (shapes differ per capacity)
    ndev = 1 if mesh is None else int(mesh.shape[mesh_axis])
    on_mesh = ndev > 1

    def body(state, cutoff):
        new_views, counts, live_max = {}, {}, {}
        for name, st in state["views"].items():
            keep_mask = st["touch"] >= cutoff
            n_evict = jnp.sum((st["gv"] & ~keep_mask).astype(jnp.int32))
            if on_mesh and n_parts:
                n_evict = jax.lax.psum(n_evict, mesh_axis)
            counts[name] = n_evict
            fn = _compact_one if n_parts == 0 else jax.vmap(_compact_one)
            hi, lo, stats, gv, touch = fn(st["hi"], st["lo"], st["stats"],
                                          st["gv"], st["touch"], keep_mask)
            # live occupancy after compaction — per partition on the
            # (P, C) layout, whose MAX bounds the shrink-pass capacity
            if n_parts == 0:
                n_live = jnp.sum(gv.astype(jnp.int32))
            else:
                n_live = jnp.max(jnp.sum(gv.astype(jnp.int32), axis=1))
                if on_mesh:
                    n_live = jax.lax.pmax(n_live, mesh_axis)
            live_max[name] = n_live
            new_st = dict(hi=hi, lo=lo, stats=stats, gv=gv, touch=touch)
            if st.get("keep") is not None:
                nt = stats[f"t_{name}"]
                ov = (overlap_keep if n_parts == 0
                      else jax.vmap(overlap_keep))
                new_st["keep"] = ov(gv, nt, stats["one"] - nt)
            new_views[name] = new_st
        new_state = dict(state)
        new_state["views"] = new_views
        return new_state, counts, live_max

    if on_mesh:
        view_spec = P(mesh_axis, None) if n_parts else P()
        state_spec = dict(views=view_spec)
        if has_stream:
            state_spec["stream"] = P()

        def evict_program(state, cutoff):
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(state_spec, P()),
                                 out_specs=(state_spec, P(), P()),
                                 check_vma=False)(state, cutoff)
    else:
        def evict_program(state, cutoff):
            return body(state, cutoff)

    # keep_unused: the overlap keep mask is RECOMPUTED (not read) by the
    # body; without it jit would prune the donated input params and their
    # buffers could never alias the fresh keep outputs (donation must be
    # total — the jaxpr audit asserts every state leaf is consumed)
    return counted_jit(evict_program, donate_argnums=(0,),
                       keep_unused=True)
