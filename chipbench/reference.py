"""The plain float64 reference of the engine's semantics, and its control.

Copied from ``src/repro/core/oracle.py`` (``coarsen_oracle``,
``cem_group_stats_oracle``, ``ate_att_oracle``) and the reference glue of
``src/repro/launch/smoke.py``, and kept here so that no program change can
move it. It imports nothing of the program and reads only the generated
relation and the configuration.

Semantics (the paper's CEM over a streamed relation):

* coarsening: a categorical covariate is its integer code clipped to
  ``[0, card)``; a continuous one is ``searchsorted(cutpoints, x,
  side="right")`` over float32 values and float32 cutpoints
  ``linspace(lo, hi, k + 1)[1:-1]`` (the paper's CASE/WHEN view);
* a view of treatment ``T`` groups rows by the coarsened buckets of
  ``T``'s covariates plus the query dims; the base view by every dim;
* every group once ingested stays live, also when retractions bring its
  count to zero; it is matched while it holds a treated and a control row;
* each group keeps the decomposable sums ``one, y, yy`` and, for every
  treatment ``S``, ``t_S, yt_S, yyt_S``;
* ATE and ATT over the matched groups of a subpopulation are the paper's
  eq. 4 (weights: group size, and treated count);
* a group is exported under a 64-bit key that packs its buckets
  (:func:`pack_keys`), so a comparison group by group also catches a
  group filed under another key.

:class:`Groups` sums the reference in float64; :func:`control_state`
and :func:`control_estimate` are the same computation in bfloat16, the
next precision below the float32 the configurations state, which every
comparison must tell apart from the program.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

BASE = "__base__"


# ------------------------------------------------------------- schema
def dims_of(cfg: Mapping) -> Dict[str, Tuple[str, ...]]:
    """Dims of every view: treatment -> sorted(covariates + query dims),
    and the base view over all of them."""
    q = tuple(cfg["query_dims"])
    views = {t: tuple(sorted(set(cov) | set(q)))
             for t, cov in cfg["treatments"].items()}
    views[BASE] = tuple(sorted(set(q).union(*map(set, views.values()))))
    return views


def bucketize(rel: Mapping[str, np.ndarray], coarsening: Mapping
              ) -> Dict[str, np.ndarray]:
    """Bucket id (int64) of every row for every coarsened dim."""
    out = {}
    for name, c in coarsening.items():
        x = rel[name]
        if "categorical" in c:
            out[name] = np.clip(x.astype(np.int64), 0,
                                int(c["categorical"]) - 1)
        else:
            lo, hi, k = c["equal_width"]
            cut = np.linspace(lo, hi, int(k) + 1)[1:-1].astype(np.float32)
            out[name] = np.searchsorted(cut, x.astype(np.float32),
                                        side="right").astype(np.int64)
    return out


def n_buckets(c: Mapping) -> int:
    return int(c["categorical"]) if "categorical" in c else int(
        c["equal_width"][2])


class Groups:
    """The groups of every view over a fixed set of rows (the rows ever
    ingested): per view, each row's group index and each group's
    buckets. Sums over any weighting of those rows are then bincounts."""

    def __init__(self, cfg: Mapping, rel: Mapping[str, np.ndarray]):
        self.cfg = cfg
        self.treatments = tuple(sorted(cfg["treatments"]))
        b = bucketize(rel, cfg["coarsening"])
        self.index: Dict[str, np.ndarray] = {}
        self.buckets: Dict[str, Dict[str, np.ndarray]] = {}
        for view, dims in dims_of(cfg).items():
            cards = [n_buckets(cfg["coarsening"][d]) for d in dims]
            key = np.zeros(len(rel["dep_delay"]), np.int64)
            for d, card in zip(dims, cards):
                key = key * card + b[d]
            uniq, inv = np.unique(key, return_inverse=True)
            self.index[view] = inv.astype(np.int64)
            buckets = {}
            for d, card in zip(dims[::-1], cards[::-1]):
                uniq, buckets[d] = np.divmod(uniq, card)
            self.buckets[view] = {d: buckets[d] for d in dims}
        self.treat = {t: rel[t].astype(np.float64) for t in self.treatments}

    def n_groups(self, view: str) -> int:
        return len(next(iter(self.buckets[view].values())))

    def weights(self, y: np.ndarray, w: np.ndarray,
                rows: Optional[slice] = None) -> Dict[str, np.ndarray]:
        """Each row's contribution to every group sum, over rows ``rows``
        (all by default), with outcome ``y`` and row weights ``w`` (+1
        ingested, -1 retracted, 0 absent) of those rows."""
        rows = slice(None) if rows is None else rows
        y = np.asarray(y, np.float64)
        w = np.asarray(w, np.float64)
        wy = w * y
        out = {"one": w, "y": wy, "yy": wy * y}
        for t in self.treatments:
            tw = w * self.treat[t][rows]
            out[f"t_{t}"] = tw
            out[f"yt_{t}"] = tw * y
            out[f"yyt_{t}"] = out[f"yt_{t}"] * y
        return out

    def sums(self, view: str, weights: Mapping[str, np.ndarray],
             rows: Optional[slice] = None) -> Dict[str, np.ndarray]:
        """float64 group sums of ``view`` over rows ``rows`` of the
        contributions ``weights`` (:meth:`weights` of the same rows)."""
        rows = slice(None) if rows is None else rows
        inv = self.index[view][rows]
        g = self.n_groups(view)
        return {k: np.bincount(inv, weights=v, minlength=g)
                for k, v in weights.items()}


def keep(sums: Mapping[str, np.ndarray], treatment: str) -> np.ndarray:
    """Matched groups: at least one treated and one control row."""
    nt = sums[f"t_{treatment}"]
    return (nt > 0) & (sums["one"] - nt > 0)


# ------------------------------------------------------------ estimates
def estimate(groups: Groups, sums: Mapping[str, np.ndarray], treatment: str,
             subpopulation: Optional[Mapping[str, Sequence[int]]] = None,
             dtype=np.float64) -> Dict[str, float]:
    """ATE and ATT (eq. 4) of ``treatment``'s view over the matched groups
    whose buckets pass ``subpopulation`` (dim -> allowed buckets), in
    ``dtype`` arithmetic. ``scale_*`` is the weighted mean of
    ``|mean_t| + |mean_c|``: the magnitude an evaluation in a lower
    precision rounds against."""
    m = keep(sums, treatment)
    for dim, allowed in (subpopulation or {}).items():
        m &= np.isin(groups.buckets[treatment][dim], list(allowed))
    cast = lambda a: np.asarray(a[m]).astype(dtype)
    n_t = cast(sums[f"t_{treatment}"])
    n_c = cast(sums["one"]) - n_t
    yt = cast(sums[f"yt_{treatment}"])
    yc = cast(sums["y"]) - yt
    mean_t, mean_c = yt / n_t, yc / n_c
    diff = mean_t - mean_c
    mag = (np.abs(mean_t) + np.abs(mean_c)).astype(np.float64)
    n_b = n_t + n_c

    def wmean(w, x):
        tot = w.sum(dtype=dtype)
        return float((w * x).sum(dtype=dtype) / tot) if tot > 0 else 0.0
    n_t64 = np.asarray(sums[f"t_{treatment}"])[m]
    n_b64 = np.asarray(sums["one"])[m]
    return dict(ate=wmean(n_b, diff), att=wmean(n_t, diff),
                scale_ate=wmean(n_b64, mag), scale_att=wmean(n_t64, mag),
                n_matched_treated=int(n_t64.sum()),
                n_matched_control=int((n_b64 - n_t64).sum()),
                n_groups=int(m.sum()))


def control_estimate(groups: Groups, sums, treatment, subpopulation=None):
    """The reference estimator computed in bfloat16: the control."""
    return estimate(groups, sums, treatment, subpopulation,
                    dtype=ml_dtypes.bfloat16)


def estimate_error(got: Mapping, want: Mapping, estimand: str
                   ) -> Tuple[float, int]:
    """(|got - want| / scale of the chosen estimand, number of count
    fields that differ)."""
    err = abs(float(got[estimand]) - want[estimand])
    scale = want[f"scale_{estimand}"]
    rel = err / scale if scale > 0 else (0.0 if err == 0 else float("inf"))
    bad = sum(int(got[f]) != int(want[f]) for f in
              ("n_matched_treated", "n_matched_control", "n_groups"))
    return rel, bad


# ---------------------------------------------------------------- state
#: float32 holds every integer up to here exactly
EXACT_F32 = float(1 << 24)


def _names(treatments: Sequence[str]) -> List[str]:
    ts = sorted(treatments)
    return (["one"] + [f"t_{t}" for t in ts] + ["y"]
            + [f"yt_{t}" for t in ts] + ["yy"] + [f"yyt_{t}" for t in ts])


def _matrix(sums: Mapping[str, np.ndarray], matched: Optional[np.ndarray],
            treatments: Sequence[str]) -> np.ndarray:
    cols = [np.asarray(sums[s], np.float64) for s in _names(treatments)]
    if matched is not None:
        cols.append(np.asarray(matched).astype(np.float64))
    return np.stack(cols, axis=1)


def key_width(c: Mapping) -> int:
    """Bits of one dim in a packed group key: enough for its buckets,
    at least one."""
    return max(1, int(np.ceil(np.log2(max(2, n_buckets(c))))))


def pack_keys(cfg: Mapping, dims: Sequence[str],
              buckets: Mapping[str, np.ndarray]) -> np.ndarray:
    """The 64-bit group key of each bucket tuple, in the layout the
    engine exports (``key_hi << 32 | key_lo``): the view's dims in name
    order, the first in the highest bits, each ``key_width`` bits wide."""
    key = np.zeros(len(buckets[dims[0]]), np.uint64)
    for d in sorted(dims):
        w = np.uint64(key_width(cfg["coarsening"][d]))
        key = (key << w) | buckets[d].astype(np.uint64)
    return key


def engine_state(hi: np.ndarray, lo: np.ndarray,
                 sums: Mapping[str, np.ndarray],
                 matched: Optional[np.ndarray],
                 treatments: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
    """A view as the engine exported it: its 64-bit group keys and one
    row per group (the counts, the outcome sums, the sums of squares
    and, for a treatment view, the matched flag), sorted by key."""
    key = (np.asarray(hi, np.uint64) << np.uint64(32)) | np.asarray(
        lo, np.uint64)
    order = np.argsort(key, kind="stable")
    return key[order], _matrix(sums, matched, treatments)[order]


def reference_state(groups: Groups, y: np.ndarray, w: np.ndarray,
                    y_peak: np.ndarray
                    ) -> Dict[str, Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """float64 group state of every view for rows weighted ``w``: the
    sorted group keys and the :func:`engine_state` matrix, with a matrix
    of bounds in the same order: each entry's sum over every row ever
    ingested at its largest outcome ``y_peak``. Every contribution is at
    least 0, so no value the entry held during the run passed its bound;
    where the bound is below 2^24, float32 arithmetic on it was exact."""
    out = {}
    ws = groups.weights(y, w)
    wb = groups.weights(y_peak, np.ones(len(y)))
    for view, dims in dims_of(groups.cfg).items():
        s = groups.sums(view, ws)
        b = groups.sums(view, wb)
        base = view == BASE
        mat = _matrix(s, None if base else keep(s, view), groups.treatments)
        bound = _matrix(b, None if base else np.ones(len(mat)),
                        groups.treatments)
        key = pack_keys(groups.cfg, dims, groups.buckets[view])
        order = np.argsort(key, kind="stable")
        out[view] = (key[order], mat[order], bound[order])
    return out


def state_error(got: Mapping[str, Tuple[np.ndarray, np.ndarray]],
                want: Mapping[str, Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]],
                notes: Optional[List[str]] = None) -> Dict[str, float]:
    """Compare a state (view -> :func:`engine_state` keys and matrix)
    with :func:`reference_state`'s, group by group.

    ``views_differing``: views whose set of group keys differs (a group
    missing, added, or filed under another key).
    ``exact_mismatch``: entries whose bound is below 2^24, so that float32
    held every value they took exactly (every count, outcome sum and flag,
    and the sums of squares of all but the largest groups), that differ.
    ``large_rel_err``: the largest ``|got - want| / |want|`` over the
    other entries, sums of squares that float32 accumulation rounds (0
    where there are none).
    The first differences are described in ``notes``."""
    out = {"views_differing": 0, "exact_mismatch": 0, "large_rel_err": 0.0}
    notes = [] if notes is None else notes
    for view, (wk, w, bound) in want.items():
        gk, g = got.get(view, (None, None))
        if gk is None or gk.shape != wk.shape or (gk != wk).any():
            out["views_differing"] += 1
            if gk is not None:
                extra = np.setdiff1d(gk, wk)
                missing = np.setdiff1d(wk, gk)
                notes.append(f"{view}: {len(missing)} groups missing, "
                             f"{len(extra)} not in the reference")
            continue
        exact = bound < EXACT_F32
        bad = np.argwhere((g != w) & exact)
        out["exact_mismatch"] += len(bad)
        notes += [f"{view} key {wk[r]} column {c}: {g[r, c]!r} vs "
                  f"{w[r, c]!r} (bound {bound[r, c]!r})"
                  for r, c in bad[:5]]
        inexact = ~exact & (w != 0)
        if inexact.any():
            rel = np.abs(g - w)[inexact] / np.abs(w)[inexact]
            out["large_rel_err"] = max(out["large_rel_err"],
                                       float(rel.max()))
    return out


def control_state(groups: Groups, ops: List[Tuple[slice, np.ndarray,
                                                  float]]
                  ) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """The state accumulated in bfloat16: each operation ``(rows, y,
    sign)`` adds its exact group sums into a bfloat16 table, rounding
    after every batch, as a bfloat16 engine state would."""
    bf = ml_dtypes.bfloat16
    acc: Dict[str, Dict[str, np.ndarray]] = {}
    for rows, y, sign in ops:
        ws = groups.weights(y, np.full(len(y), sign), rows)
        for view in groups.index:
            s = groups.sums(view, ws, rows)
            if view not in acc:
                acc[view] = {k: v.astype(bf) for k, v in s.items()}
            else:
                acc[view] = {k: (acc[view][k].astype(np.float32)
                                 + s[k].astype(np.float32)).astype(bf)
                             for k in s}
    out = {}
    for view, dims in dims_of(groups.cfg).items():
        sums = {k: v.astype(np.float64) for k, v in acc[view].items()}
        key = pack_keys(groups.cfg, dims, groups.buckets[view])
        order = np.argsort(key, kind="stable")
        mat = _matrix(sums, None if view == BASE else keep(sums, view),
                      groups.treatments)
        out[view] = (key[order], mat[order])
    return out
