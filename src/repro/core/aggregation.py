"""User-centric aggregation (paper Eq. 5) over parameter pytrees.

Stacked-client params: every leaf carries a leading client dim m.  The
aggregation is a weighted mix along that dim:

    θ_i^t = Σ_j W[i,j] θ_j^{t-1/2}        (unicast / full personalization)
    θ̂_c  = Σ_j Ŵ[c,j] θ_j ; θ_i = θ̂_{a(i)}  (m_t streams, group broadcast)

Under pjit with the client dim sharded over a mesh axis, the einsum lowers
to the corresponding collective (all-gather+mix or k weighted all-reduces);
`repro.core.distributed` provides explicit shard_map schedules for the same
math, and `repro.kernels.mixing_aggregate` the Pallas PS-side kernel.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.streams import StreamPlan


def _mix_leaf(w: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """(k,m) x (m, ...) -> (k, ...) in the leaf's dtype.

    Inputs stay in the leaf dtype (so any collective the mix lowers to moves
    bf16, not fp32); the contraction accumulates in fp32.  HIGHEST keeps
    float32 leaves and weights in float32 on a TPU, whose default rounds
    matmul inputs to bf16 (the weights would no longer sum to one)."""
    out = jax.lax.dot_general(
        w.astype(leaf.dtype), leaf, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return out.astype(leaf.dtype)


def mix_pytree(stacked_params: Any, w: jnp.ndarray) -> Any:
    """Apply an aggregation-rule matrix w (k, m) to all leaves (m, ...)."""
    return jax.tree_util.tree_map(lambda l: _mix_leaf(w, l), stacked_params)


def user_centric_aggregate(stacked_params: Any, w: jnp.ndarray) -> Any:
    """Full personalization: every client gets its own mixed model (m -> m)."""
    return mix_pytree(stacked_params, w)


def fedavg_aggregate(stacked_params: Any, n: jnp.ndarray) -> Any:
    """FedAvg: one weighted mean, broadcast back to all m clients."""
    m = n.shape[0]
    w = jnp.broadcast_to((n / jnp.sum(n))[None, :], (m, m))
    return mix_pytree(stacked_params, w)


def stream_aggregate(stacked_params: Any, plan: StreamPlan) -> Any:
    """m_t-stream aggregation: mix to centroids then group-broadcast."""
    mixed = mix_pytree(stacked_params, plan.centroids)          # (k, ...)
    return jax.tree_util.tree_map(
        lambda l: jnp.take(l, plan.assignment, axis=0), mixed)  # (m, ...)


def downlink_models(w_or_plan) -> int:
    """Number of distinct models the PS must transmit (comm-model input)."""
    if isinstance(w_or_plan, StreamPlan):
        return int(w_or_plan.centroids.shape[0])
    return int(w_or_plan.shape[0])
