"""Average-treatment-effect estimation (paper Eq. 1 / Eq. 4).

Given balanced groups b (CEM subclasses or propensity subclasses),

  tau_ATE = E_b[ E[Y|T=1, b] - E[Y|T=0, b] ]        (Eq. 4)

weighted by group probability n_b / N over the matched subset. We also
provide ATT weighting (treated-count weights — the standard CEM estimand)
and a per-unit weight vector ("cem weights") so any downstream weighted
estimator (e.g. weighted least squares) can consume the match.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core import groupby
from repro.core.cem import CEMGroups


@dataclasses.dataclass(frozen=True)
class ATEEstimate:
    """Both estimands of one causal query, plus match diagnostics.

    Every query path — offline :func:`estimate_ate`, the online engines'
    ``ate()``, and the batched/serving path (``ate_batch``,
    :class:`repro.core.serving.QuerySpec`) — returns this same record;
    a ``QuerySpec``'s ``estimand`` only selects which field the serving
    layer reports (``QuerySpec.select``), so ATE and ATT twins of one
    subpopulation share a single estimate (and cache entry).

    ``state_version`` is the MVCC snapshot tag: online-engine estimates
    carry the committed state version they were answered at (-1 for the
    offline estimators, which have no versioned state). Two estimates with
    the same spec and the same ``state_version`` are bitwise identical."""

    ate: jnp.ndarray          # Eq. 4, group-probability weights
    att: jnp.ndarray          # treated-weighted
    n_matched_treated: jnp.ndarray
    n_matched_control: jnp.ndarray
    n_groups: jnp.ndarray
    variance: jnp.ndarray     # conservative within-group variance of ATE
    state_version: int = -1   # engine snapshot version (see core/online.py)


def _group_means(groups: CEMGroups):
    nt = jnp.where(groups.keep, groups.n_treated, 0.0)
    nc = jnp.where(groups.keep, groups.n_control, 0.0)
    mean_t = jnp.where(nt > 0, groups.sum_y_t / jnp.maximum(nt, 1e-9), 0.0)
    mean_c = jnp.where(nc > 0, groups.sum_y_c / jnp.maximum(nc, 1e-9), 0.0)
    return nt, nc, mean_t, mean_c


def _neyman_variance(keep, nt, nc, mean_t, mean_c, sum_yy_t, sum_yy_c,
                     sum_fn=jnp.sum):
    """Conservative within-group (Neyman) variance of the ATE from
    decomposable per-arm first and second moments."""
    var_t = sum_yy_t / jnp.maximum(nt, 1e-9) - mean_t ** 2
    var_c = sum_yy_c / jnp.maximum(nc, 1e-9) - mean_c ** 2
    n_b = nt + nc
    n_tot = jnp.maximum(sum_fn(n_b), 1e-9)
    se2_b = (var_t / jnp.maximum(nt, 1.0) + var_c / jnp.maximum(nc, 1.0))
    return sum_fn(jnp.where(keep, (n_b / n_tot) ** 2 * se2_b, 0.0))


def estimate_ate_from_stats(keep: jnp.ndarray, n_treated: jnp.ndarray,
                            n_control: jnp.ndarray, sum_y_t: jnp.ndarray,
                            sum_y_c: jnp.ndarray,
                            sum_yy_t: jnp.ndarray = None,
                            sum_yy_c: jnp.ndarray = None,
                            sum_fn=jnp.sum) -> ATEEstimate:
    """ATE/ATT straight from decomposable group stats (no row access).

    This is the estimator the online engine runs over materialized cuboid
    stat tables: O(#groups), independent of data size. With per-arm second
    moments (``sum_yy_t``/``sum_yy_c`` — the cuboid's ``yy``-family columns)
    the Neyman within-group variance is included; without them it is 0.

    ``sum_fn`` is the cross-group reduction. The online query pipelines
    pass the capacity-invariant canonical sum
    (:func:`repro.kernels.segment_stats.canonical_sum`), which makes the
    estimate a bitwise-deterministic function of the key-sorted group
    content ALONE — independent of padded vector length, partition count
    or capacity-growth history — so the replicated, partitioned, fused
    and batched (vmapped spec-table) query paths all return identical
    f32 bits for identical group stats."""
    nt = jnp.where(keep, n_treated, 0.0)
    nc = jnp.where(keep, n_control, 0.0)
    mean_t = jnp.where(nt > 0, sum_y_t / jnp.maximum(nt, 1e-9), 0.0)
    mean_c = jnp.where(nc > 0, sum_y_c / jnp.maximum(nc, 1e-9), 0.0)
    diff = mean_t - mean_c
    n_b = nt + nc
    n_tot = jnp.maximum(sum_fn(n_b), 1e-9)
    ate = sum_fn(jnp.where(keep, n_b * diff, 0.0)) / n_tot
    t_tot = jnp.maximum(sum_fn(nt), 1e-9)
    att = sum_fn(jnp.where(keep, nt * diff, 0.0)) / t_tot
    if sum_yy_t is None or sum_yy_c is None:
        var = jnp.float32(0.0)
    else:
        var = _neyman_variance(keep, nt, nc, mean_t, mean_c,
                               sum_yy_t, sum_yy_c, sum_fn=sum_fn)
    return ATEEstimate(ate=ate, att=att,
                       n_matched_treated=sum_fn(nt),
                       n_matched_control=sum_fn(nc),
                       n_groups=jnp.sum(keep.astype(jnp.int32)),
                       variance=var)


def estimate_ate(groups: CEMGroups,
                 y: jnp.ndarray = None, treatment: jnp.ndarray = None,
                 matched_valid: jnp.ndarray = None) -> ATEEstimate:
    """ATE/ATT from group stats. If (y, treatment, matched_valid) are given,
    a within-group variance estimate is included (else 0)."""
    est = estimate_ate_from_stats(groups.keep, groups.n_treated,
                                  groups.n_control, groups.sum_y_t,
                                  groups.sum_y_c)
    if y is None:
        return est
    nt, nc, mean_t, mean_c = _group_means(groups)
    g = groups.grouping
    w = matched_valid.astype(jnp.float32)
    t = treatment.astype(jnp.float32) * w
    c = (1.0 - treatment.astype(jnp.float32)) * w
    yf = y.astype(jnp.float32)
    sums = groupby.segment_sums(g, {"yy_t": t * yf * yf,
                                    "yy_c": c * yf * yf})
    var = _neyman_variance(groups.keep, nt, nc, mean_t, mean_c,
                           sums["yy_t"], sums["yy_c"])
    return dataclasses.replace(est, variance=var)


def cem_weights(groups: CEMGroups, treatment: jnp.ndarray,
                matched_valid: jnp.ndarray) -> jnp.ndarray:
    """Per-unit CEM weights (Iacus-King-Porro): treated units weight 1;
    control units in group b weight (n_t_b / n_c_b) * (N_c / N_t)."""
    g = groups.grouping
    nt_rows = groupby.broadcast_to_rows(g, groups.n_treated)
    nc_rows = groupby.broadcast_to_rows(g, groups.n_control)
    Nt, Nc = groups.matched_counts()
    t = treatment.astype(jnp.float32)
    w_control = (nt_rows / jnp.maximum(nc_rows, 1e-9)) * (Nc / jnp.maximum(Nt, 1e-9))
    w = jnp.where(t > 0, 1.0, w_control)
    return jnp.where(matched_valid, w, 0.0)


def difference_in_means(y: jnp.ndarray, treatment: jnp.ndarray,
                        valid: jnp.ndarray) -> jnp.ndarray:
    """Naive (confounded) estimator E[Y|T=1] - E[Y|T=0] — Eq. 2 applied
    without balancing; the paper's cautionary baseline."""
    w = valid.astype(jnp.float32)
    t = treatment.astype(jnp.float32) * w
    c = (1.0 - treatment.astype(jnp.float32)) * w
    yf = y.astype(jnp.float32)
    mean_t = jnp.sum(t * yf) / jnp.maximum(jnp.sum(t), 1e-9)
    mean_c = jnp.sum(c * yf) / jnp.maximum(jnp.sum(c), 1e-9)
    return mean_t - mean_c
