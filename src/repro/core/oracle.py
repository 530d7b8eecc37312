"""Pure-numpy reference implementations — the "R package" proxy.

The paper's Table 3 compares ZaliQL against R's MatchIt/CEM packages. We
have no R offline, so these hash-map/loop implementations play that role:
they are written in the most obvious way possible (dict group-by, O(n^2)
scans), independently of the JAX engine, and double as oracles for unit,
property and kernel tests.
"""
from __future__ import annotations

from typing import Dict, Mapping, Sequence, Tuple

import numpy as np


def coarsen_oracle(x: np.ndarray, cutpoints: Sequence[float]) -> np.ndarray:
    return np.searchsorted(np.asarray(cutpoints), x, side="right").astype(
        np.int32)


def cem_oracle(buckets: Mapping[str, np.ndarray], t: np.ndarray,
               valid: np.ndarray) -> Tuple[np.ndarray, Dict]:
    """Dict-based CEM: returns (matched mask, {group key -> row idx list})."""
    names = sorted(buckets)
    n = len(t)
    groups: Dict[tuple, list] = {}
    for i in range(n):
        if not valid[i]:
            continue
        key = tuple(int(buckets[m][i]) for m in names)
        groups.setdefault(key, []).append(i)
    matched = np.zeros(n, dtype=bool)
    kept = {}
    for key, rows in groups.items():
        ts = [int(t[i]) for i in rows]
        if 0 in ts and 1 in ts:
            kept[key] = rows
            for i in rows:
                matched[i] = True
    return matched, kept


def ate_oracle(groups: Dict, t: np.ndarray, y: np.ndarray) -> float:
    """Eq. 4 with group-probability weights over the matched subset."""
    n_tot = sum(len(rows) for rows in groups.values())
    acc = 0.0
    for rows in groups.values():
        rt = [i for i in rows if t[i] == 1]
        rc = [i for i in rows if t[i] == 0]
        diff = np.mean(y[rt]) - np.mean(y[rc])
        acc += len(rows) / n_tot * diff
    return float(acc)


def att_oracle(groups: Dict, t: np.ndarray, y: np.ndarray) -> float:
    n_t = sum(sum(1 for i in rows if t[i] == 1) for rows in groups.values())
    acc = 0.0
    for rows in groups.values():
        rt = [i for i in rows if t[i] == 1]
        rc = [i for i in rows if t[i] == 0]
        diff = np.mean(y[rt]) - np.mean(y[rc])
        acc += len(rt) / n_t * diff
    return float(acc)


def awmd_oracle(groups: Dict, t: np.ndarray, x: np.ndarray) -> float:
    """Eq. 5 for one covariate."""
    n_tot = sum(len(rows) for rows in groups.values())
    acc = 0.0
    for rows in groups.values():
        rt = [i for i in rows if t[i] == 1]
        rc = [i for i in rows if t[i] == 0]
        acc += len(rows) / n_tot * abs(np.mean(x[rt]) - np.mean(x[rc]))
    return float(acc)


def knn_oracle(U_treated: np.ndarray, U_control: np.ndarray,
               control_valid: np.ndarray, k: int, caliper: float
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Brute-force k-NN with caliper; ties broken by (distance, index)."""
    nt = len(U_treated)
    dist = np.full((nt, k), np.inf, dtype=np.float64)
    idx = np.full((nt, k), -1, dtype=np.int64)
    for i in range(nt):
        d = np.linalg.norm(U_control - U_treated[i], axis=1)
        d = np.where(control_valid, d, np.inf)
        order = np.lexsort((np.arange(len(d)), d))[:k]
        m = min(k, len(order))
        dist[i, :m] = d[order]
        idx[i, :m] = order
    dist = np.where(dist <= caliper, dist, np.inf)
    return dist, idx


def ntile_oracle(ps: np.ndarray, valid: np.ndarray, n: int) -> np.ndarray:
    nv = int(valid.sum())
    order = np.lexsort((np.arange(len(ps)), np.where(valid, ps, np.inf)))
    bucket = np.full(len(ps), n, dtype=np.int32)
    for rank, row in enumerate(order[:nv]):
        bucket[row] = min(rank * n // nv, n - 1)
    return bucket


def greedy_match_oracle(edges, n_rows: int, k: int):
    """edges: list of (dist, control, treated) — greedy sweep by distance."""
    edges = sorted(edges, key=lambda e: (e[0], e[1], e[2]))
    used_c = np.zeros(n_rows, bool)
    cnt_t = np.zeros(n_rows, np.int64)
    taken = []
    for d, c, t in edges:
        if not np.isfinite(d):
            continue
        if used_c[c] or cnt_t[t] >= k:
            continue
        used_c[c] = True
        cnt_t[t] += 1
        taken.append((d, c, t))
    return taken


def logistic_oracle(X: np.ndarray, t: np.ndarray, valid: np.ndarray,
                    n_iter: int = 64, ridge: float = 1e-4) -> np.ndarray:
    """Standardized Newton logistic regression; returns propensity scores."""
    v = valid.astype(np.float64)
    n = max(v.sum(), 1.0)
    mean = (X * v[:, None]).sum(0) / n
    var = (v[:, None] * (X - mean) ** 2).sum(0) / n
    std = np.sqrt(np.maximum(var, 1e-12))
    Xs = (X - mean) / std
    Xb = np.concatenate([Xs, np.ones((len(X), 1))], axis=1)
    w = np.zeros(Xb.shape[1])
    for _ in range(n_iter):
        p = 1 / (1 + np.exp(-Xb @ w))
        g = Xb.T @ (v * (p - t)) + ridge * w
        s = v * p * (1 - p) + 1e-6
        H = (Xb * s[:, None]).T @ Xb + ridge * np.eye(Xb.shape[1])
        w -= np.linalg.solve(H, g)
    return 1 / (1 + np.exp(-(Xb @ w)))


def cem_group_stats_oracle(buckets: Mapping[str, np.ndarray], t: np.ndarray,
                           y: np.ndarray, valid: np.ndarray) -> Dict:
    """Vectorized float64 twin of :func:`cem_oracle` for large row counts:
    the CEM groups of the valid rows (one per distinct bucket tuple),
    their treated/control counts and outcome sums, kept only where both
    arms are present. Also reports the number of groups before and after
    the overlap filter and the largest per-group sum of ``y**2`` (the
    engine's f32 group stats are exact while it stays below 2^24 for
    integer outcomes). ``tests/test_chip_smoke.py`` holds it equal to the
    dict oracle."""
    names = sorted(buckets)
    v = np.asarray(valid, bool)
    cols = [np.asarray(buckets[m])[v].astype(np.int64) for m in names]
    key = np.zeros(int(v.sum()), np.int64)
    for c in cols:
        key = key * (int(c.max(initial=0)) + 1) + c
    _, first, inv = np.unique(key, return_index=True, return_inverse=True)
    g = len(first)
    tt = np.asarray(t)[v].astype(np.float64)
    yv = np.asarray(y)[v].astype(np.float64)
    n = np.bincount(inv, minlength=g).astype(np.float64)
    n_t = np.bincount(inv, weights=tt, minlength=g)
    n_c = n - n_t
    keep = (n_t > 0) & (n_c > 0)
    return dict(
        buckets={m: c[first][keep] for m, c in zip(names, cols)},
        n_t=n_t[keep], n_c=n_c[keep],
        sum_y_t=np.bincount(inv, weights=yv * tt, minlength=g)[keep],
        sum_y_c=np.bincount(inv, weights=yv * (1 - tt), minlength=g)[keep],
        n_groups_all=g, n_groups_matched=int(keep.sum()),
        max_sum_yy=float(np.bincount(inv, weights=yv * yv,
                                     minlength=g).max(initial=0.0)))


def ate_att_oracle(groups: Mapping, subpopulation: Mapping = None) -> Dict:
    """Eq. 4 ATE and the ATT over the matched groups of
    :func:`cem_group_stats_oracle` whose buckets pass ``subpopulation``
    (dim -> allowed buckets) — :func:`ate_oracle` / :func:`att_oracle`
    over the same groups. ``scale_ate`` / ``scale_att`` are the
    weight-averaged ``|mean_t| + |mean_c|``, the magnitude an f32
    evaluation of the same sums rounds against."""
    m = np.ones(len(groups["n_t"]), bool)
    for dim, allowed in (subpopulation or {}).items():
        m &= np.isin(groups["buckets"][dim], list(allowed))
    n_t, n_c = groups["n_t"][m], groups["n_c"][m]
    mean_t = groups["sum_y_t"][m] / np.maximum(n_t, 1)
    mean_c = groups["sum_y_c"][m] / np.maximum(n_c, 1)
    diff, mag = mean_t - mean_c, np.abs(mean_t) + np.abs(mean_c)
    n_b = n_t + n_c

    def wmean(w, x):
        tot = w.sum()
        return float((w * x).sum() / tot) if tot > 0 else 0.0
    return dict(ate=wmean(n_b, diff), att=wmean(n_t, diff),
                scale_ate=wmean(n_b, mag), scale_att=wmean(n_t, mag),
                n_matched_treated=int(n_t.sum()),
                n_matched_control=int(n_c.sum()), n_groups=int(m.sum()))
