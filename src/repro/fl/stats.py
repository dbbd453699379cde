"""Pre-round client statistics on stacked federated data.

Used by the UCFL strategy's `setup` (Eq. 6 inputs) but generic: any
strategy that needs full-dataset gradients or the Eq. 7 variance proxy at
the common initialization can reuse these.

Both passes differentiate through every client's whole (padded) dataset,
so they run as one compiled program that takes the clients a chunk at a
time: at the paper's size (100 clients x 1,796 padded LeNet samples) the
whole stack at once needs 11.7 GiB of temporaries on a v5e chip, a chunk
of `CHUNK_SAMPLES` about 2 GiB.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.similarity import flatten_pytree
from repro.data.federated import FederatedData

# padded samples whose activations one chunk of clients holds at once
CHUNK_SAMPLES = 16_384


def _per_client(fn, fed: FederatedData) -> jnp.ndarray:
    """``fn(x_i, y_i)`` for every client, ``CHUNK_SAMPLES`` at a time."""
    batch = max(1, CHUNK_SAMPLES // fed.x.shape[1])
    if batch >= fed.m:
        return jax.vmap(fn)(fed.x, fed.y)
    return jax.lax.map(lambda xy: fn(*xy), (fed.x, fed.y), batch_size=batch)


@partial(jax.jit, static_argnums=0)
def full_client_gradients(loss_fn, params, fed: FederatedData) -> jnp.ndarray:
    """ĝ_i over each client's (padded) dataset; (m, D) float32."""

    def one(x_i, y_i):
        g, _ = jax.grad(loss_fn, has_aux=True)(params, {"x": x_i, "y": y_i})
        return flatten_pytree(g)

    return _per_client(one, fed)


@partial(jax.jit, static_argnums=(0, 3))
def sigma2_estimates(loss_fn, params, fed: FederatedData, k_batches: int
                     ) -> jnp.ndarray:
    """Eq. 7 on contiguous K-way splits of each client's data."""
    n_max = fed.x.shape[1]
    bs = n_max // k_batches

    def one(x_i, y_i):
        gfull, _ = jax.grad(loss_fn, has_aux=True)(
            params, {"x": x_i, "y": y_i})
        gfull = flatten_pytree(gfull)
        devs = []
        for k in range(k_batches):
            sl = {"x": x_i[k * bs:(k + 1) * bs], "y": y_i[k * bs:(k + 1) * bs]}
            gk, _ = jax.grad(loss_fn, has_aux=True)(params, sl)
            devs.append(jnp.sum((flatten_pytree(gk) - gfull) ** 2))
        return jnp.mean(jnp.stack(devs))

    return _per_client(one, fed)
