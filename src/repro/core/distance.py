"""Distance measures for matching (paper Fig. 1).

- Propensity-score distance |E(x_i) - E(x_j)|  (1-D!)
- Mahalanobis distance (x_i - x_j)' Sigma^{-1} (x_j - x_j)
- Coarsened distance (0 if same coarsened cell, inf otherwise) — that case
  is CEM and handled by repro.core.cem.

Every matmul here runs at ``HIGHEST`` precision: the TPU's default f32
matmul rounds its inputs to bf16, and the distances then miss the float64
k-NN reference by up to 0.27 (``test_knn_quadratic_matches_bruteforce`` on
a v5e), far outside what f32 arithmetic explains.

Mahalanobis is expressed in an MXU-friendly form: with L = chol(Sigma^{-1}),
d(i,j) = ||L^T x_i - L^T x_j||^2, so a one-time feature rotation turns it
into squared Euclidean distance and the matching kernel only ever computes
||u_i - u_j||^2 = |u_i|^2 + |u_j|^2 - 2 u_i.u_j  (a matmul).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.data.columnar import Table

HIGHEST = jax.lax.Precision.HIGHEST


def masked_covariance(X: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    w = valid.astype(jnp.float32)[:, None]
    n = jnp.maximum(jnp.sum(w), 2.0)
    mean = jnp.sum(X * w, axis=0) / n
    Xc = (X - mean) * w
    return jnp.matmul(Xc.T, Xc, precision=HIGHEST) / (n - 1.0)


def mahalanobis_transform(X: jnp.ndarray, valid: jnp.ndarray,
                          ridge: float = 1e-6) -> jnp.ndarray:
    """Rotate features so Euclidean distance == Mahalanobis distance."""
    d = X.shape[1]
    sigma = masked_covariance(X, valid) + ridge * jnp.eye(d)
    sigma_inv = jnp.linalg.inv(sigma)
    L = jnp.linalg.cholesky(sigma_inv)
    return jnp.matmul(X.astype(jnp.float32), L, precision=HIGHEST)


def features(table: Table, names: Sequence[str]) -> jnp.ndarray:
    return jnp.stack([table[n].astype(jnp.float32) for n in names], axis=-1)


def pairwise_sqdist(U: jnp.ndarray, V: jnp.ndarray) -> jnp.ndarray:
    """(n, d) x (m, d) -> (n, m) squared Euclidean distances via matmul."""
    un = jnp.sum(U * U, axis=1, keepdims=True)
    vn = jnp.sum(V * V, axis=1, keepdims=True)
    return jnp.maximum(
        un + vn.T - 2.0 * jnp.matmul(U, V.T, precision=HIGHEST), 0.0)


def ps_distance_features(ps: jnp.ndarray) -> jnp.ndarray:
    """Propensity distance as 1-D Euclidean features."""
    return ps.astype(jnp.float32)[:, None]
