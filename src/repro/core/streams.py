"""Personalized-stream reduction (paper §III-B).

k-means over the rows of the mixing matrix W; the m_t centroids become the
personalized streams and each client is served its cluster's centroid rule
(group broadcast instead of unicast).  The silhouette score over the rows
guides the choice of m_t, per the paper.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class StreamPlan(NamedTuple):
    centroids: jnp.ndarray     # (k, m) — the Ŵ aggregation rules
    assignment: jnp.ndarray    # (m,) int32 — client -> stream
    inertia: jnp.ndarray       # scalar, final k-means objective


# the k-means algebra runs in full float32: a TPU matmul at default
# precision rounds its inputs to bf16, which the distance cancellation
# below and the row-stochastic centroids cannot absorb
_mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def _pairwise_sq(a, b):
    return (jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :]
            - 2.0 * _mm(a, b.T))


def kmeans(rows: jnp.ndarray, k: int, *, n_iter: int = 50,
           key=None, drop_diag: bool = True) -> StreamPlan:
    """Lloyd's algorithm with greedy k-means++ style seeding (deterministic
    given `key`).  rows: (m, m) mixing-weight vectors.

    drop_diag: cluster on the OFF-DIAGONAL collaboration profile.  Each raw
    row is dominated by its own diagonal (self-weight at a different
    coordinate per client), so raw rows of same-group clients are mutually
    *distant* in L2 and Lloyd's degenerates to one blob + singletons at
    small m.  Zeroing the diagonal (and renormalizing) clusters clients by
    who they collaborate with — the quantity the paper's protocol actually
    groups by.  Centroids are then re-fit as the mean of the ORIGINAL rows
    per cluster, which spreads each member's self-weight over its cluster
    (the group-broadcast semantics).
    """
    m = rows.shape[0]
    k = int(min(k, m))
    key = jax.random.PRNGKey(0) if key is None else key
    raw = rows.astype(jnp.float32)
    if drop_diag and m > 1 and rows.shape[0] == rows.shape[1]:
        x = raw * (1.0 - jnp.eye(m, dtype=jnp.float32))
        x = x / jnp.maximum(jnp.sum(x, axis=1, keepdims=True), 1e-9)
    else:
        x = raw

    # k-means++ seeding
    first = jax.random.randint(key, (), 0, m)
    centers = [x[first]]
    for _ in range(1, k):
        d = jnp.min(_pairwise_sq(x, jnp.stack(centers)), axis=1)
        centers.append(x[jnp.argmax(d)])          # farthest-point (deterministic)
    cents = jnp.stack(centers)

    def step(cents, _):
        d = _pairwise_sq(x, cents)                # (m, k)
        assign = jnp.argmin(d, axis=1)
        oh = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # (m, k)
        counts = jnp.maximum(jnp.sum(oh, axis=0), 1.0)
        new = _mm(oh.T, x) / counts[:, None]
        # keep empty clusters where they were
        new = jnp.where((jnp.sum(oh, axis=0) > 0)[:, None], new, cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=n_iter)
    d = _pairwise_sq(x, cents)
    assign = jnp.argmin(d, axis=1).astype(jnp.int32)
    inertia = jnp.sum(jnp.min(d, axis=1))
    # re-fit centroids on the ORIGINAL rows of each cluster and renormalize
    # to remain aggregation rules (row-stochastic)
    oh = jax.nn.one_hot(assign, k, dtype=jnp.float32)
    counts = jnp.maximum(jnp.sum(oh, axis=0), 1.0)
    cents = _mm(oh.T, raw) / counts[:, None]
    cents = cents / jnp.maximum(jnp.sum(cents, axis=1, keepdims=True), 1e-9)
    return StreamPlan(cents, assign, inertia)


def silhouette_score(rows: jnp.ndarray, assignment: jnp.ndarray,
                     k: int) -> jnp.ndarray:
    """Mean silhouette over samples (euclidean).  Degenerate clusters -> 0."""
    x = rows.astype(jnp.float32)
    m = x.shape[0]
    d = jnp.sqrt(jnp.maximum(_pairwise_sq(x, x), 0.0))        # (m, m)
    oh = jax.nn.one_hot(assignment, k, dtype=jnp.float32)     # (m, k)
    counts = jnp.sum(oh, axis=0)                              # (k,)
    sums = _mm(d, oh)                                         # (m, k)
    own = counts[assignment]
    a = jnp.where(own > 1,
                  jnp.take_along_axis(sums, assignment[:, None], 1)[:, 0]
                  / jnp.maximum(own - 1, 1), 0.0)
    other = jnp.where(oh > 0, jnp.inf, sums / jnp.maximum(counts[None, :], 1))
    b = jnp.min(other, axis=1)
    s = jnp.where((own > 1) & jnp.isfinite(b),
                  (b - a) / jnp.maximum(jnp.maximum(a, b), 1e-9), 0.0)
    return jnp.mean(s)


def select_num_streams(rows: jnp.ndarray, candidates=None, *,
                       key=None) -> Tuple[int, dict]:
    """Silhouette-guided m_t selection (paper: silhouette over the w_i's)."""
    m = rows.shape[0]
    if candidates is None:
        candidates = [k for k in (2, 3, 4, 6, 8) if k < m]
    scores = {}
    for k in candidates:
        plan = kmeans(rows, k, key=key)
        scores[k] = float(silhouette_score(rows, plan.assignment, k))
    best = max(scores, key=scores.get)
    return best, scores
