"""Nearest-neighbour matching (paper §3.1, Figs. 2-3).

NNMWR (with replacement): for each treated unit, its k nearest control
units within the caliper — the paper's window-function view. Two engines:

* ``knn_quadratic``: tiled all-pairs distance + running top-k. This is the
  paper's "by necessity quadratic" general path; the inner tile is the
  Pallas kernel (`repro.kernels.knn_topk`), here a pure-jnp block loop.
* ``knn_sorted_1d``: beyond-paper fast path for 1-D distances (the dominant
  propensity-score case): sort controls, searchsorted each treated unit,
  scan a +/-k candidate window — O(N log N), not quadratic.

NNMNR (without replacement): the paper's greedy half-approximation (its
Fig. 3): sort candidate edges by distance, sweep keeping the 1:k invariant.
Inherently sequential (Prop. 1 shows the exact problem is NLOGSPACE-hard),
expressed as a `lax.scan` over the globally distance-sorted edge list.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.launch.trace import counted_jit

BIG = jnp.float32(3.4e38)


@dataclasses.dataclass(frozen=True)
class MatchResult:
    """k matches per row. Arrays are (N, k) aligned to the *original* row
    order; rows that are not valid treated units have all-invalid matches."""

    idx: jnp.ndarray       # (N, k) int32 control row indices
    dist: jnp.ndarray      # (N, k) f32
    ok: jnp.ndarray        # (N, k) bool — match exists & within caliper
    treated_mask: jnp.ndarray  # (N,) bool — rows that sought matches

    def n_matched_treated(self):
        return jnp.sum((jnp.any(self.ok, axis=1) & self.treated_mask
                        ).astype(jnp.int32))


def _topk_merge(run_d, run_i, new_d, new_i, k):
    d = jnp.concatenate([run_d, new_d], axis=1)
    i = jnp.concatenate([run_i, new_i], axis=1)
    neg = -d
    vals, pos = jax.lax.top_k(neg, k)
    return -vals, jnp.take_along_axis(i, pos, axis=1)


@partial(counted_jit, static_argnames=("k", "block"))
def knn_quadratic(U_treated: jnp.ndarray, U_control: jnp.ndarray,
                  control_valid: jnp.ndarray, k: int, caliper: float,
                  block: int = 1024) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-pairs k-NN: (Nt, d) vs (Nc, d) -> (Nt, k) (dist, idx).

    Control blocks stream through a running top-k (the same loop the Pallas
    kernel and the distributed ring k-NN use). Invalid controls get +BIG.
    """
    nt, d = U_treated.shape
    nc = U_control.shape[0]
    pad = (-nc) % block
    Uc = jnp.pad(U_control, ((0, pad), (0, 0)))
    cv = jnp.pad(control_valid, (0, pad))
    nb = (nc + pad) // block
    Ucb = Uc.reshape(nb, block, d)
    cvb = cv.reshape(nb, block)

    tn = jnp.sum(U_treated * U_treated, axis=1, keepdims=True)

    def body(carry, blk):
        run_d, run_i = carry
        Ub, vb, base = blk
        cn = jnp.sum(Ub * Ub, axis=1)[None, :]
        # HIGHEST: the TPU's default f32 matmul rounds inputs to bf16 (see
        # repro.core.distance)
        dot = jnp.matmul(U_treated, Ub.T, precision=jax.lax.Precision.HIGHEST)
        dist = jnp.maximum(tn + cn - 2.0 * dot, 0.0)
        dist = jnp.where(vb[None, :], dist, BIG)
        idx = (base + jnp.arange(block, dtype=jnp.int32))[None, :]
        idx = jnp.broadcast_to(idx, dist.shape)
        bk = min(k, block)
        nd, np_ = jax.lax.top_k(-dist, bk)
        ni = jnp.take_along_axis(idx, np_, axis=1)
        return _topk_merge(run_d, run_i, -nd, ni, k), None

    run_d = jnp.full((nt, k), BIG, jnp.float32)
    run_i = jnp.full((nt, k), -1, jnp.int32)
    bases = jnp.arange(nb, dtype=jnp.int32) * block
    (run_d, run_i), _ = jax.lax.scan(body, (run_d, run_i), (Ucb, cvb, bases))
    run_d = jnp.sqrt(run_d)  # report Euclidean (sq kept internally)
    run_d = jnp.where(run_d <= caliper, run_d, BIG)
    return run_d, run_i


@partial(counted_jit, static_argnames=("k", "window"))
def knn_sorted_1d(x_treated: jnp.ndarray, x_control: jnp.ndarray,
                  control_valid: jnp.ndarray, k: int, caliper: float,
                  window: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """1-D k-NN fast path (propensity distance). O(N log N).

    window defaults to k (candidates = k left + k right of the insertion
    point, which always contains the true k nearest in 1-D).
    """
    w = window or k
    nc = x_control.shape[0]
    xc = jnp.where(control_valid, x_control.astype(jnp.float32), BIG)
    iota = jnp.arange(nc, dtype=jnp.int32)
    xs, perm = jax.lax.sort((xc, iota), num_keys=1, is_stable=True)
    pos = jnp.searchsorted(xs, x_treated.astype(jnp.float32))
    offs = jnp.arange(-w, w, dtype=jnp.int32)  # 2w candidates
    cand = pos[:, None] + offs[None, :]
    inb = (cand >= 0) & (cand < nc)
    cand = jnp.clip(cand, 0, nc - 1)
    cd = jnp.abs(xs[cand] - x_treated[:, None].astype(jnp.float32))
    cd = jnp.where(inb & (xs[cand] < BIG), cd, BIG)
    nd, np_ = jax.lax.top_k(-cd, k)
    idx = jnp.take_along_axis(perm[cand], np_, axis=1)
    dist = -nd
    dist = jnp.where(dist <= caliper, dist, BIG)
    return dist, idx


def nnmwr(U: jnp.ndarray, treatment: jnp.ndarray, valid: jnp.ndarray,
          k: int, caliper: float, engine: str = "auto",
          block: int = 1024) -> MatchResult:
    """k:1 NNM with replacement over feature matrix U (N, d).

    All N rows are passed as "treated" queries for shape stability; rows with
    treatment==0 or invalid are masked out of the result.
    """
    t = treatment.astype(bool) & valid
    c = (~treatment.astype(bool)) & valid
    if engine == "auto":
        engine = "sorted1d" if U.shape[1] == 1 else "quadratic"
    if engine == "sorted1d":
        dist, idx = knn_sorted_1d(U[:, 0], U[:, 0], c, k, caliper)
    else:
        dist, idx = knn_quadratic(U, U, c, k, caliper, block=block)
    ok = (dist < BIG) & t[:, None]
    return MatchResult(idx=idx, dist=dist, ok=ok, treated_mask=t)


def nnmwr_att(y: jnp.ndarray, result: MatchResult) -> jnp.ndarray:
    """ATT from a with-replacement match: mean over matched treated units of
    (y_i - mean(y of matched controls))."""
    yf = y.astype(jnp.float32)
    okf = result.ok.astype(jnp.float32)
    n_ok = jnp.sum(okf, axis=1)
    ym = jnp.sum(jnp.where(result.ok, yf[jnp.clip(result.idx, 0, None)], 0.0),
                 axis=1) / jnp.maximum(n_ok, 1e-9)
    has = n_ok > 0
    diff = jnp.where(has, yf - ym, 0.0)
    return jnp.sum(diff) / jnp.maximum(jnp.sum(has.astype(jnp.float32)), 1e-9)


@partial(counted_jit, static_argnames=("n_rows", "k"))
def greedy_nnmnr(cand_dist: jnp.ndarray, cand_idx: jnp.ndarray,
                 treated_rows: jnp.ndarray, n_rows: int, k: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy without-replacement sweep (paper Fig. 3).

    cand_dist/cand_idx: (Nt, m) candidate matches per treated row (from a
    with-replacement k'-NN with k' = m >= k). Edges are globally sorted by
    distance and swept with a `lax.scan`; a control is taken at most once, a
    treated row takes at most k controls.

    Returns (take: (Nt, m) bool over candidate slots, order broken by global
    distance rank) — the 1/2-approximation of optimal matching.
    """
    nt, m = cand_dist.shape
    flat_d = cand_dist.reshape(-1)
    flat_c = cand_idx.reshape(-1)
    flat_t = jnp.repeat(treated_rows, m)
    order = jnp.argsort(flat_d)  # stable ascending

    def body(state, e):
        used_c, cnt_t = state
        d, cidx, tidx = e
        cidx_c = jnp.clip(cidx, 0, n_rows - 1)
        tidx_c = jnp.clip(tidx, 0, n_rows - 1)
        ok = (d < BIG) & (~used_c[cidx_c]) & (cnt_t[tidx_c] < k)
        used_c = used_c.at[cidx_c].set(used_c[cidx_c] | ok)
        cnt_t = cnt_t.at[tidx_c].add(ok.astype(jnp.int32))
        return (used_c, cnt_t), ok

    used_c = jnp.zeros((n_rows,), bool)
    cnt_t = jnp.zeros((n_rows,), jnp.int32)
    _, taken = jax.lax.scan(
        body, (used_c, cnt_t),
        (flat_d[order], flat_c[order], flat_t[order]))
    take_flat = jnp.zeros((nt * m,), bool).at[order].set(taken)
    return take_flat.reshape(nt, m), order


def nnmnr(U: jnp.ndarray, treatment: jnp.ndarray, valid: jnp.ndarray,
          k: int, caliper: float, m_candidates: Optional[int] = None,
          engine: str = "auto") -> MatchResult:
    """k:1 NNM without replacement = with-replacement candidates (m >= k per
    treated unit) + greedy global sweep."""
    m = m_candidates or max(4 * k, 8)
    wr = nnmwr(U, treatment, valid, k=m, caliper=caliper, engine=engine)
    treated_rows = jnp.arange(U.shape[0], dtype=jnp.int32)
    take, _ = greedy_nnmnr(jnp.where(wr.ok, wr.dist, BIG), wr.idx,
                           treated_rows, U.shape[0], k)
    ok = wr.ok & take
    return MatchResult(idx=wr.idx, dist=wr.dist, ok=ok,
                       treated_mask=wr.treated_mask)
