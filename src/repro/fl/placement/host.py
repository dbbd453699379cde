"""Host `vmap` placement: all clients stacked on one device (DESIGN.md §3).

This is the paper-scale backend (m=20..100, LeNet) and the reference
semantics: a `run_federated` call with `HostVmap()` is bit-identical to
the pre-placement engine.  The jitted local-update step is cached across
calls keyed on the (loss_fn, FLConfig) fields it closes over, so sweep
drivers (`benchmarks/paper_experiments.py`) re-entering `run_federated`
per (scenario × algorithm × trial) stop recompiling identical programs.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.core import stream_aggregate, user_centric_aggregate
from repro.core.streams import StreamPlan
from repro.data.federated import FederatedData
from repro.fl.placement.base import (Placement, stack_params,
                                     where_clients)
from repro.optim import apply_updates, sgd


def make_client_update(loss_fn: Callable, opt, fl):
    """Returns f(params_i, opt_i, data_i, n_i, key) -> (params_i', opt_i')
    running `local_steps` SGD steps on mini-batches drawn from client i."""

    def client_update(params_i, opt_i, x_i, y_i, n_i, key):
        n_slots = x_i.shape[0]

        def step(carry, k):
            # named scopes (DESIGN.md §3h): the backward pass, remat's
            # recompute included, carries `transpose(` inside `loss`
            p, o = carry
            with jax.named_scope("local_update/batch"):
                idx = jax.random.randint(k, (fl.batch_size,), 0, 1 << 30)
                idx = idx % jnp.maximum(n_i.astype(jnp.int32), 1) % n_slots
                batch = {"x": x_i[idx], "y": y_i[idx]}
            with jax.named_scope("local_update/loss"):
                grads, _ = jax.grad(loss_fn, has_aux=True)(p, batch)
            with jax.named_scope("local_update/optimizer"):
                upd, o = opt.update(grads, o, p)
                p = apply_updates(p, upd)
            return (p, o), None

        keys = jax.random.split(key, fl.local_steps)
        # NOTE: do not be tempted to unroll this scan — unrolling lets XLA
        # fuse across step boundaries differently in the eventful per-round
        # jit vs the fused superstep program (§3c), breaking their
        # final-params bit-parity at local_steps >= 2
        (p, o), _ = jax.lax.scan(step, (params_i, opt_i), keys)
        return p, o

    return client_update


class _UpdateConfig:
    """The FLConfig fields `make_client_update` closes over (hash key)."""

    def __init__(self, local_steps: int, batch_size: int):
        self.local_steps = local_steps
        self.batch_size = batch_size


@functools.lru_cache(maxsize=16)
def cached_update(loss_fn: Callable, local_steps: int, batch_size: int,
                  lr: float, momentum: float, state_dtype=None,
                  donate: bool = False) -> Tuple[Any, Callable]:
    """(opt, jit(vmap(client_update))) memoized on everything the step
    closes over — repeated `run_federated` calls with the same config
    reuse the compiled executable instead of re-tracing per run.
    ``donate=True`` donates the stacked params/opt-state arguments, so the
    step updates in place instead of holding two copies of the client
    stack (the engine's buffer-donation memory lever)."""
    opt = sgd(lr, momentum=momentum, state_dtype=state_dtype)
    client_update = make_client_update(
        loss_fn, opt, _UpdateConfig(local_steps, batch_size))
    step = jax.vmap(client_update)
    return opt, (jax.jit(step, donate_argnums=(0, 1)) if donate
                 else jax.jit(step))


@functools.lru_cache(maxsize=8)
def _eval_fn(apply_acc: Callable):
    return jax.jit(jax.vmap(lambda p, x, y: apply_acc(p, {"x": x, "y": y})))


def reduce_scores(accs) -> Tuple[float, float]:
    """(mean, worst) reduction of the per-client score vector — shared by
    the eventful `evaluate` and the fused-eval superstep replay
    (DESIGN.md §3c/§3e) so the two paths reduce identically."""
    return float(jnp.mean(accs)), float(jnp.min(accs))


def evaluate(apply_acc: Callable, stacked_params, fed: FederatedData
             ) -> Tuple[float, float]:
    """(mean, worst) validation accuracy across clients, personalized models."""
    return reduce_scores(
        _eval_fn(apply_acc)(stacked_params, fed.x_val, fed.y_val))


class HostVmap(Placement):
    """Single-device stacked-client placement (reference semantics)."""

    name = "host_vmap"

    def build_update(self, loss_fn: Callable, fl, *,
                     donate: bool = False) -> Tuple[Any, Callable]:
        return cached_update(loss_fn, fl.local_steps, fl.batch_size,
                             fl.lr, fl.momentum,
                             getattr(fl, "opt_state_dtype", None), donate)

    def stack(self, params0: Any, m: int) -> Any:
        return stack_params(params0, m)

    def update_cohort(self, update_fn, idx, keep, stacked, opt_state,
                      x, y, n, ckeys):
        # gather the k cohort rows, update them, scatter the kept ones
        # back: O(k) local-update compute per async event instead of O(m)
        # (the jitted step retraces once for the (k, ...) shapes)
        take = lambda t: jax.tree_util.tree_map(lambda l: l[idx], t)
        sub, sub_opt = take(stacked), take(opt_state)
        new_sub, new_opt = update_fn(sub, sub_opt, x[idx], y[idx], n[idx],
                                     ckeys[idx])
        new_sub = where_clients(keep, new_sub, sub)
        new_opt = where_clients(keep, new_opt, sub_opt)
        scatter = lambda full, s: jax.tree_util.tree_map(
            lambda l, ls: l.at[idx].set(ls), full, s)
        return scatter(stacked, new_sub), scatter(opt_state, new_opt)

    def mix(self, stacked: Any, w: jnp.ndarray) -> Any:
        return user_centric_aggregate(stacked, w)

    def mix_plan(self, stacked: Any, plan: StreamPlan) -> Any:
        return stream_aggregate(stacked, plan)

    def evaluate(self, acc_fn: Callable, stacked: Any, fed: FederatedData
                 ) -> Tuple[float, float]:
        return evaluate(acc_fn, stacked, fed)
