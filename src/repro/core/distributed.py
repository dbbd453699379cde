"""Explicit shard_map collective schedules for user-centric aggregation.

The pjit einsum in `aggregation.py` lets GSPMD choose collectives (the
baseline we roofline).  These schedules pin the communication pattern:

  * `mix_unicast_shard_map`  — all-gather the client-stacked params over the
    client axis, mix locally with the full W.  Receive volume ≈ (m-1)/m · mP
    per client group: the paper's m-fold downlink.
  * `mix_streams_shard_map`  — each shard sends its k weighted copies into a
    psum; every shard then selects its assigned stream.  Volume ∝ k·P: the
    paper's group-broadcast protocol, and the §Perf lever.

Both operate on a params pytree whose leaves have leading client dim m
sharded over `axis`; inside shard_map each shard holds m/axis_size clients.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# every mix contracts float32 weights and params at full precision: a TPU
# otherwise rounds matmul inputs to bf16 (`aggregation._mix_leaf`)
HIGHEST = jax.lax.Precision.HIGHEST


def mix_unicast_shard_map(mesh, axis: str, params: Any, w: jnp.ndarray) -> Any:
    """θ_i ← Σ_j W[i,j] θ_j via all-gather over `axis` + local mix.

    params leaves: (m, ...) sharded P(axis, ...); w: (m, m) replicated.
    """
    m = w.shape[0]
    size = mesh.shape[axis]
    mm = m // size

    def body(w_rep, p_local):
        idx = jax.lax.axis_index(axis)
        gathered = jax.tree_util.tree_map(
            lambda l: jax.lax.all_gather(l, axis, axis=0, tiled=True), p_local)
        w_rows = jax.lax.dynamic_slice_in_dim(w_rep, idx * mm, mm, 0)  # (mm, m)
        return jax.tree_util.tree_map(
            lambda g: jnp.tensordot(w_rows.astype(jnp.float32),
                                    g.astype(jnp.float32), axes=(1, 0),
                                    precision=HIGHEST).astype(g.dtype),
            gathered)

    pspec = jax.tree_util.tree_map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), params)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), pspec),
                       out_specs=pspec, check_vma=False)
    return fn(w, params)


def mix_streams_shard_map(mesh, axis: str, params: Any,
                          centroids: jnp.ndarray,
                          assignment: jnp.ndarray) -> Any:
    """θ_i ← θ̂_{a(i)}, θ̂ = Ŵ θ via one psum of k weighted copies.

    centroids: (k, m); assignment: (m,) int32.  Volume ∝ k·P (k streams).
    """
    k, m = centroids.shape
    size = mesh.shape[axis]
    mm = m // size

    def body(w_rep, assign, p_local):
        idx = jax.lax.axis_index(axis)
        w_cols = jax.lax.dynamic_slice_in_dim(w_rep, idx * mm, mm, 1)  # (k, mm)
        contrib = jax.tree_util.tree_map(
            lambda l: jnp.tensordot(w_cols.astype(jnp.float32),
                                    l.astype(jnp.float32), axes=(1, 0),
                                    precision=HIGHEST),
            p_local)                                            # (k, ...)
        mixed = jax.lax.psum(contrib, axis)                     # all shards: (k, ...)
        my_assign = jax.lax.dynamic_slice_in_dim(assign, idx * mm, mm, 0)
        return jax.tree_util.tree_map(
            lambda l, ref: jnp.take(l, my_assign, axis=0).astype(ref.dtype),
            mixed, p_local)

    pspec = jax.tree_util.tree_map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), params)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), pspec),
                       out_specs=pspec, check_vma=False)
    return fn(centroids, assignment, params)


MIX_SCHEDULES = ("gspmd", "shard_map_streams", "shard_map_unicast")


def mix_schedule(mesh, axes, params: Any, w: jnp.ndarray, assignment=None, *,
                 schedule: str = "gspmd") -> Any:
    """One entry point for every mixing-collective schedule.

    ``assignment=None`` means ``w`` is a full per-client matrix (one row
    per client, the unicast protocol); otherwise ``w`` is (k, m) centroid
    rules and ``assignment`` maps clients to streams.  ``axes`` are the
    mesh axes carrying the client dim — empty means no mesh placement and
    the einsum baseline is used regardless of ``schedule``.
    """
    if schedule == "gspmd" or not axes:
        return mix_einsum(params, w, assignment)
    axis = axes[0] if len(axes) == 1 else axes
    if schedule == "shard_map_streams":
        if assignment is None:           # full matrix: one stream per client
            assignment = jnp.arange(w.shape[0], dtype=jnp.int32)
        return mix_streams_shard_map(mesh, axis, params, w, assignment)
    if schedule == "shard_map_unicast":
        full_w = w if assignment is None else jnp.take(w, assignment, axis=0)
        return mix_unicast_shard_map(mesh, axis, params, full_w)
    raise ValueError(f"unknown mixing schedule {schedule!r}; "
                     f"one of {sorted(MIX_SCHEDULES)}")


def mix_einsum(params: Any, w: jnp.ndarray, assignment=None) -> Any:
    """pjit/GSPMD baseline: plain einsum mix (+ optional stream selection).
    Inputs stay in the param dtype (collectives move bf16); fp32 accumulate."""
    def leaf(l):
        out = jax.lax.dot_general(w.astype(l.dtype), l,
                                  (((1,), (0,)), ((), ())),
                                  precision=HIGHEST,
                                  preferred_element_type=jnp.float32)
        return out.astype(l.dtype)
    mixed = jax.tree_util.tree_map(leaf, params)
    if assignment is None:
        return mixed
    return jax.tree_util.tree_map(
        lambda l: jnp.take(l, assignment, axis=0), mixed)
