"""Distribution-similarity statistics (paper §III-A).

The special pre-training round: the PS broadcasts a probe model θ̂; every
client i computes (a) the full-dataset gradient ĝ_i = (1/n_i) Σ ∇ℓ and
(b) the gradient-variance estimate σ_i² over K local mini-batch resamples
(Eq. 7).  The PS then forms the pairwise score
Δ_{i,j} = ||ĝ_i − ĝ_j||²  (an estimate of the squared mean-gradient
discrepancy between P_i and P_j).

On the TPU mesh Δ is a Gram-matrix computation over m gradient vectors of
dimension D — `repro.kernels.pairwise_sqdist` is the Pallas kernel for it;
`delta_matrix` below is the pure-jnp implementation (also its oracle).
"""
from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp


def flatten_pytree(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])


def full_gradient(loss_fn: Callable, params, data) -> jnp.ndarray:
    """ĝ_i: flat full-dataset gradient of `loss_fn(params, data)`."""
    g = jax.grad(lambda p: loss_fn(p, data))(params)
    return flatten_pytree(g)


def client_gradients(loss_fn: Callable, params, datasets: Sequence) -> jnp.ndarray:
    """Stack ĝ_i for every client: (m, D)."""
    return jnp.stack([full_gradient(loss_fn, params, d) for d in datasets])


def delta_matrix(grads: jnp.ndarray) -> jnp.ndarray:
    """Δ_{i,j} = ||g_i - g_j||² from stacked gradients (m, D).

    Computed via the Gram matrix (one pass over D): ||g_i||² + ||g_j||² − 2⟨g_i,g_j⟩,
    in full float32 — the difference cancels, so the bf16 inputs of a TPU
    matmul at default precision would swamp it.
    """
    g = grads.astype(jnp.float32)
    sq = jnp.sum(g * g, axis=-1)
    gram = jnp.matmul(g, g.T, precision=jax.lax.Precision.HIGHEST)
    d = sq[:, None] + sq[None, :] - 2.0 * gram
    return jnp.maximum(d, 0.0)


def sigma_estimates(loss_fn: Callable, params, datasets: Sequence, *,
                    n_batches: int = 5, key=None) -> jnp.ndarray:
    """σ_i² (Eq. 7): mean squared deviation of K mini-batch gradients from ĝ_i.

    Each dataset is a dict of arrays with a leading sample dim; batches are
    contiguous K-way splits (a fixed partition, as in the paper).
    """
    sigmas = []
    for data in datasets:
        n = jax.tree_util.tree_leaves(data)[0].shape[0]
        g_full = full_gradient(loss_fn, params, data)
        K = max(2, min(n_batches, n))
        bounds = [round(k * n / K) for k in range(K + 1)]
        devs = []
        for k in range(K):
            sl = jax.tree_util.tree_map(lambda a: a[bounds[k]:bounds[k + 1]], data)
            g_k = full_gradient(loss_fn, params, sl)
            devs.append(jnp.sum((g_k - g_full) ** 2))
        sigmas.append(jnp.mean(jnp.stack(devs)))
    return jnp.stack(sigmas)


def similarity_round(loss_fn: Callable, probe_params, datasets: Sequence, *,
                     n_batches: int = 5) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The full pre-training round.  Returns (Δ (m,m), σ² (m,), n (m,))."""
    grads = client_gradients(loss_fn, probe_params, datasets)
    delta = delta_matrix(grads)
    sigma2 = sigma_estimates(loss_fn, probe_params, datasets,
                             n_batches=n_batches)
    n = jnp.array([jax.tree_util.tree_leaves(d)[0].shape[0] for d in datasets],
                  jnp.float32)
    return delta, sigma2, n
