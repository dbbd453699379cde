"""Placement protocol: where clients live and how their models move.

A `Placement` owns everything about the *physical* layout of a federated
round (DESIGN.md §3): stacking the common initialization into the
client-stacked pytree, building the (cached, jitted) local-update step,
placing the client datasets and per-round PRNG keys, rolling back
non-participants, applying a mixing matrix `W` or a `StreamPlan`, and
evaluating the personalized models.  Strategies (DESIGN.md §4) stay
placement-agnostic: they route every matrix/plan application through
`RoundContext.mix` / `RoundContext.mix_plan`, which dispatch here.

Two backends ship:

  * `HostVmap`   — all clients in one stacked pytree on the default
    device; local updates are one `jit(vmap(client_update))`.  Bit-for-bit
    the pre-placement `run_federated` semantics.
  * `MeshShardMap` — clients sharded over a device mesh axis; the mixing
    becomes explicit collectives (GSPMD einsum or hand-scheduled
    `shard_map`, selected by `schedule=`).
"""
from __future__ import annotations

import abc
from typing import Any, Callable, ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.streams import StreamPlan
from repro.data.federated import FederatedData


def stack_params(params: Any, m: int) -> Any:
    """Broadcast a single-model pytree to the (m, ...) client stack."""
    return jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[None], (m,) + l.shape).copy(), params)


def where_clients(mask: jnp.ndarray, new: Any, old: Any) -> Any:
    """Per-client select over stacked pytrees (leading dim m)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(mask.reshape((-1,) + (1,) * (a.ndim - 1)),
                               a, b), new, old)


class Placement(abc.ABC):
    """One client-placement backend; see module docstring."""

    name: ClassVar[str]

    # Which codec implementation the channel uplink (DESIGN.md §3b) runs
    # on this backend: "pallas" = the repro.kernels quantize / top-k
    # threshold kernels (single-device stacks); "jnp" = the pure-jnp
    # oracle math, which GSPMD shards over the client axis (bit-identical
    # for qsgd; top-k differs only in tie handling).
    codec_backend: ClassVar[str] = "pallas"

    @abc.abstractmethod
    def build_update(self, loss_fn: Callable, fl: Any, *,
                     donate: bool = False) -> Tuple[Any, Callable]:
        """Returns ``(opt, update_fn)`` where ``update_fn(stacked, opt_state,
        x, y, n, ckeys) -> (stacked', opt_state')`` runs every client's
        local SGD.  Implementations cache the jitted step across calls
        (sweeps re-enter `run_federated` with identical configs).
        ``donate=True`` donates the input stacked/opt buffers to the step
        (they are dead after the call) — the engine requests it when no
        sampler needs rollback and the strategy never reads `prev`."""

    @abc.abstractmethod
    def stack(self, params0: Any, m: int) -> Any:
        """Place the common initialization as the (m, ...) client stack."""

    def init_opt(self, opt: Any, stacked: Any) -> Any:
        return jax.vmap(opt.init)(stacked)

    def place_data(self, fed: FederatedData) -> Tuple[Any, Any, Any]:
        """Place the stacked client train arrays ``(x, y, n)``."""
        return fed.x, fed.y, fed.n

    def place_keys(self, ckeys: jnp.ndarray) -> jnp.ndarray:
        """Place the (m, 2) per-client round keys."""
        return ckeys

    def place_stack(self, tree: Any, m: int) -> Any:
        """Place an ALREADY-stacked (m, ...) pytree on this backend (the
        serving plane hands request batches / decoded parameter stacks
        through here; `stack` is its broadcast-from-one-model sibling).
        Host default: identity."""
        return tree

    def place_fleet(self, tree: Any, m: int) -> Any:
        """Place device-partitioned (m, d_max, ...) fleet arrays (the
        hierarchy tier's nested device axis, DESIGN.md §3f).  Dim 0 is the
        USER axis on every backend — HostVmap device_puts the stack and
        vmaps (user, device); MeshShardMap shards users across the mesh
        and the device axis rides inside each shard — so the default
        `stage` placement is exactly right on both."""
        return self.stage(tree, m)

    def select(self, mask: jnp.ndarray, new: Any, old: Any) -> Any:
        """Participation rollback: keep `old` where ``mask`` is False."""
        return where_clients(mask, new, old)

    def update_cohort(self, update_fn: Callable, idx: jnp.ndarray,
                      keep: jnp.ndarray, stacked: Any, opt_state: Any,
                      x: Any, y: Any, n: Any, ckeys: jnp.ndarray
                      ) -> Tuple[Any, Any]:
        """Run the local update for the cohort ``idx`` (k,) only, merging
        back the rows where ``keep`` (k,) is True; every other client row
        is untouched (the async runtime's per-event step, DESIGN.md §3a).

        Default: run every slot and mask — the static-layout path sharded
        placements need.  `HostVmap` overrides with a gather/scatter so an
        event costs O(k) local-update compute, not O(m)."""
        m = ckeys.shape[0]
        mask = jnp.zeros((m,), dtype=bool).at[idx].set(keep)
        upd, upd_opt = update_fn(stacked, opt_state, x, y, n, ckeys)
        return (self.select(mask, upd, stacked),
                self.select(mask, upd_opt, opt_state))

    def uplink(self, codec: Any, stacked: Any, prev: Any, ef: Any,
               key: jnp.ndarray, mask: Optional[jnp.ndarray] = None
               ) -> Tuple[Any, Any]:
        """Pass the participating clients' updates through the channel
        codec with error feedback (DESIGN.md §3b): returns the server-side
        ``(stacked', ef')``.  Rows where ``mask`` is False are untouched.
        Identity codecs return the inputs unchanged (bit-parity anchor)."""
        from repro.fl.channel import apply_uplink
        return apply_uplink(codec, stacked, prev, ef, key, mask,
                            backend=self.codec_backend)

    @abc.abstractmethod
    def mix(self, stacked: Any, w: jnp.ndarray) -> Any:
        """Apply a full per-client aggregation matrix ``w`` (m, m)."""

    @abc.abstractmethod
    def mix_plan(self, stacked: Any, plan: StreamPlan) -> Any:
        """Apply a k-stream `StreamPlan` (centroid mix + group broadcast)."""

    # ---- superstep execution (DESIGN.md §3c) ------------------------------

    def mix_traced(self, stacked: Any, w: jnp.ndarray) -> Any:
        """Trace-safe sibling of `mix`, usable inside the superstep scan
        (no jit dispatch of its own).  Default: `mix` itself — correct for
        backends whose `mix` is already pure jnp (HostVmap)."""
        return self.mix(stacked, w)

    def mix_plan_traced(self, stacked: Any, centroids: jnp.ndarray,
                        assignment: jnp.ndarray) -> Any:
        """Trace-safe sibling of `mix_plan` (plan unpacked into arrays —
        a traced scan carries arrays, not host NamedTuples)."""
        return self.mix_plan(stacked, StreamPlan(centroids, assignment,
                                                 jnp.float32(0.0)))

    def eval_traced(self, acc_fn: Callable, stacked: Any, x_val: Any,
                    y_val: Any) -> Any:
        """Per-client validation scores (m,), trace-safe — the superstep
        fuses this onto the end of the scan (DESIGN.md §3c/§3e) so the
        chunk's eval costs no extra program dispatch.  Same vmapped math
        as the eventful `evaluate`; the (mean, worst) reduction stays
        host-side (`reduce_scores`) on both paths so they cannot drift."""
        with jax.named_scope("eval"):
            return jax.vmap(lambda p, x, y: acc_fn(p, {"x": x, "y": y}))(
                stacked, x_val, y_val)

    def stage(self, tree: Any, m: int) -> Any:
        """Begin the host->device transfer of a gathered cohort pytree
        (the paging engine's H2D leg, DESIGN.md §3e).  Returns
        device-backed arrays immediately — the copy proceeds under jax's
        async dispatch, which is what lets the engine stage cohort t+1
        while cohort t's superstep is still running."""
        return jax.device_put(tree)

    def build_round(self, round_fn: Callable, *, length: int,
                    donate: bool = True,
                    eval_fn: Optional[Callable] = None) -> Callable:
        """Compile ``length`` consecutive traced rounds as ONE `lax.scan`
        superstep: returns ``fn(carry, data, consts, eval_data) ->
        (carry', outs, accs)`` where ``round_fn(carry, data, consts) ->
        (carry', out)`` is the engine-built fused round (update → select →
        codec uplink → aggregate) and ``eval_fn(stacked, eval_data)`` (if
        given) computes the chunk-end per-client scores INSIDE the same
        program — the eval dispatch disappears from the per-chunk Python.
        The carry is donated by default — the input stacked/opt/EF buffers
        are dead once the superstep returns, so buffer donation survives
        fusion.  Backends whose arrays carry shardings (MeshShardMap) rely
        on GSPMD propagating them through the scan: the carry never leaves
        the mesh between rounds."""

        def superstep(carry, data, consts, eval_data):
            carry, outs = jax.lax.scan(lambda c, _: round_fn(c, data,
                                                             consts),
                                       carry, None, length=length)
            accs = None if eval_fn is None else eval_fn(carry[1], eval_data)
            return carry, outs, accs

        return jax.jit(superstep, donate_argnums=(0,) if donate else ())

    def run_supersteps(self, round_fn: Callable, carry: Any, data: Any,
                       consts: Any, length: int, *, cache: dict,
                       donate: bool = True,
                       eval_fn: Optional[Callable] = None,
                       eval_data: Any = None) -> Tuple[Any, Any, Any]:
        """Run ``length`` fused rounds (+ the fused chunk-end eval),
        compiling (and caching in ``cache``, keyed by length) the
        superstep on first use.  The jit re-specializes per input SHAPE,
        so one cached superstep serves every cohort size — the paging
        engine (DESIGN.md §3e) relies on this to reuse executables across
        runs that differ only in population size."""
        fn = cache.get(length)
        if fn is None:
            fn = cache[length] = self.build_round(round_fn, length=length,
                                                  donate=donate,
                                                  eval_fn=eval_fn)
        return fn(carry, data, consts, eval_data)

    def cache_key(self) -> Tuple:
        """Hashable identity for the compiled-superstep cache: two
        placements with equal keys must trace identical supersteps."""
        return (type(self).__name__,)

    @abc.abstractmethod
    def evaluate(self, acc_fn: Callable, stacked: Any, fed: FederatedData
                 ) -> Tuple[float, float]:
        """(mean, worst) validation score across clients."""

    def __repr__(self) -> str:
        return f"{type(self).__name__}()"


def resolve_placement(placement: Optional["Placement"]) -> "Placement":
    """None -> the default `HostVmap` backend."""
    if placement is None:
        from repro.fl.placement.host import HostVmap
        return HostVmap()
    return placement
