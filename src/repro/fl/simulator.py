"""Federated learning round engine: placement-generic, strategy-driven.

The engine owns the generic round mechanics — client sampling, the local
update, evaluation, the analytic clock — and delegates every
algorithm-specific decision to a `Strategy` (repro.fl.strategies) and
every layout decision to a `Placement` (repro.fl.placement):

    run_federated("ucfl_k3", fed)                          # spec string
    run_federated(strategy=get_strategy("ucfl_k3"), fed=fed)  # instance
    run_federated("ucfl_k3", fed,
                  placement=MeshShardMap(schedule="shard_map_streams"))

Registered strategies: fedavg | local | oracle | ucfl | ucfl_k<k> |
cfl (Sattler et al.) | fedfomo (Zhang et al.); see DESIGN.md §4–§5.

Placements (DESIGN.md §3): `HostVmap` (default — all clients stacked on
one device, paper-scale m=20..100) and `MeshShardMap` (clients sharded
over a device mesh, mixing via schedule-selected collectives).  The
mesh CLI `repro.launch.train` drives this same engine.

Passing ``async_cfg=AsyncConfig(...)`` delegates to the event-driven
buffered-async runtime (`repro.fl.runtime`, DESIGN.md §3a): same
strategies, same placements, virtual-clock time instead of the analytic
per-round maximum.
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.federated import FederatedData
from repro.fl.channel import (Channel, ChannelCost, resolve_channel,
                              round_downlink_time, tree_bits,
                              uplink_roundtrip, zeros_like_stack)
from repro.fl.comm import SYSTEMS, SystemModel
from repro.fl.faults import (FaultMeter, crash_mask, get_robust_aggregator,
                             inject_values, resolve_fault_plan,
                             resolve_faults, screen_and_defend)
from repro.fl.placement import (HostVmap, MeshShardMap,  # noqa: F401 (re-export)
                                Placement, evaluate, make_client_update,
                                reduce_scores, resolve_placement,
                                stack_params, where_clients)
from repro.fl.stats import full_client_gradients, sigma2_estimates  # noqa: F401 (re-exported for back-compat)
from repro.fl.strategies import (ClientSampler, CommCost, RoundContext,
                                 Strategy, StrategyExtras, TracedMix,
                                 get_strategy)
from repro.models import lenet


@dataclass
class FLConfig:
    local_steps: int = 10
    batch_size: int = 64
    lr: float = 0.1
    momentum: float = 0.9
    # optimizer-state dtype policy: None = fp32 state, "param" = keep
    # momentum in the param dtype (the giants' HBM-fit knob, DESIGN.md §4)
    opt_state_dtype: Optional[str] = None
    rounds: int = 60
    sigma_batches: int = 5
    eval_every: int = 5
    fomo_candidates: int = 5
    cfl_eps1: float = 0.04
    cfl_eps2: float = 0.06
    cfl_min_rounds: int = 10


# ---------------------------------------------------------------------------
# the round engine


def default_model_init(fed: FederatedData) -> Callable:
    """LeNet sized to the scenario's images — shared with the async engine
    so both runtimes build bit-identical initializations."""
    in_size, channels = fed.x.shape[2], fed.x.shape[4]
    n_classes = int(jnp.max(fed.y)) + 1
    return lambda k: lenet.init_params(
        k, lenet.LeNetConfig(in_size=in_size, in_channels=channels,
                             n_classes=max(n_classes, 10)))


def resolve_strategy(algorithm: Union[str, Strategy, None],
                     strategy: Optional[Strategy]) -> Strategy:
    """spec-string-or-instance -> Strategy (shared by both engines)."""
    if strategy is not None:
        if algorithm is not None:
            raise TypeError("pass either `algorithm` or `strategy=`, not both")
        return strategy
    if algorithm is None:
        raise TypeError("one of `algorithm` or `strategy=` is required")
    if isinstance(algorithm, Strategy):
        return algorithm
    return get_strategy(algorithm)


def init_run(strategy: Strategy, fed: FederatedData, fl: "FLConfig",
             model_init: Optional[Callable], loss_fn: Callable,
             acc_fn: Callable, placement: Placement, seed: int,
             donate: bool = False, hierarchy: Optional[Any] = None,
             system: Optional[SystemModel] = None,
             faults: Optional[Any] = None):
    """Shared run prologue for the sync and async engines: PRNG split,
    model init, cached update step, client stack/opt/data placement,
    RoundContext and `strategy.setup`.  Returns
    ``(key, vmapped_update, stacked, opt_state, data, ctx, state)``.

    With ``hierarchy`` (a resolved `HierarchyConfig`, DESIGN.md §3f) the
    update step becomes the fleet sub-round, the data grows the nested
    device axis and the opt-state slot carries the `EdgeState`; the
    resolved `FleetPlan` rides on ``ctx.hierarchy_plan`` for the engines'
    `EdgeMeter`.  ``system`` is consumed only there (the edge link
    resolves against it, like `init_channel`'s link).  ``faults`` (a
    `FaultConfig`/spec, DESIGN.md §3g) is resolved ONCE here into the
    run's `FaultPlan` — static Byzantine set, arrival-crash stream — and
    rides on ``ctx.fault_plan`` for the engines' injector/meter (the
    `FleetPlan` pattern; None keeps the plan off and the run on the
    faults-off parity path)."""
    m = fed.m
    key = jax.random.PRNGKey(seed)
    key, kinit = jax.random.split(key)
    if model_init is None:
        model_init = default_model_init(fed)
    with jax.profiler.TraceAnnotation("fl.model_init"):
        params0 = model_init(kinit)
    if hierarchy is None:
        opt, vmapped_update = placement.build_update(loss_fn, fl,
                                                     donate=donate)
        with jax.profiler.TraceAnnotation("fl.stack"):
            stacked = placement.stack(params0, m)
            opt_state = placement.init_opt(opt, stacked)
        with jax.profiler.TraceAnnotation("fl.place_data"):
            data = placement.place_data(fed)
        plan = None
    else:
        from repro.fl.hierarchy import init_fleet_run
        vmapped_update, stacked, opt_state, data, plan = init_fleet_run(
            hierarchy, placement, loss_fn, fl, fed, params0,
            system=system, donate=donate, strategy=strategy)

    ctx = RoundContext(fed=fed, fl=fl, loss_fn=loss_fn, acc_fn=acc_fn,
                       params0=params0, seed=seed, placement=placement,
                       strategy=strategy)
    ctx.hierarchy_plan = plan
    ctx.fault_plan = resolve_fault_plan(faults, m)
    with jax.profiler.TraceAnnotation("fl.strategy_setup"):
        state = strategy.setup(ctx)
    return key, vmapped_update, stacked, opt_state, data, ctx, state


def finalize_history(history: "History", strategy: Strategy, state: Any,
                     keep_state: bool, stacked: Any, opt_state: Any
                     ) -> "History":
    """Shared run epilogue: typed extras, the legacy extra dict, and the
    optional final device-resident state."""
    history.extras = strategy.extras(state)
    history.extra["comm_per_round"] = list(history.comm)
    if history.extras is not None:
        history.extra.update(dataclasses.asdict(history.extras))
    if keep_state:
        history.final_params, history.final_opt_state = stacked, opt_state
    return history


def init_channel(channel: Optional[Channel], ctx: "RoundContext",
                 stacked: Any, system: Optional[SystemModel], m: int):
    """Shared channel prologue for the sync and async engines (so their
    §3b semantics can't drift, like `init_run` for the round prologue):
    payload bits, resolved link profile and the error-feedback residual
    stack.  Returns ``(payload, link, model_bits, ef, channel)`` — all
    None/0 when no channel is attached.  The link is resolved FIRST
    (validating its spec even when no ``system`` will consume it, against
    the default wired model, so ``extra["channel"]`` records it
    consistently), then the codec is bound to it — rate-adaptive codecs
    pick their per-client parameters here, so callers must use the
    RETURNED channel from this point on."""
    if channel is None:
        return None, None, 0, None, None
    model_bits = tree_bits(ctx.params0)
    link = channel.resolve_link(system if system is not None
                                else SYSTEMS["wired"], model_bits, m)
    codec = channel.codec.bind_link(link, ctx.params0)
    if codec is not channel.codec:
        channel = dataclasses.replace(channel, codec=codec)
    ef = None if codec.is_identity else zeros_like_stack(stacked)
    payload = codec.payload_bits(ctx.params0)
    return payload, link, model_bits, ef, channel


def per_client_uplink_bits(channel: Optional[Channel], ctx: "RoundContext",
                           payload: Optional[int],
                           m: int) -> Optional[np.ndarray]:
    """(m,) per-client uplink payload vector when the bound codec's bits
    are NOT uniform (rate-adaptive codecs), else None — keeping the fixed-
    codec accounting on its exact scalar path."""
    if channel is None:
        return None
    vec = channel.codec.per_client_bits(ctx.params0, m)
    return None if np.all(vec == payload) else vec


def channel_uplink(placement: Placement, channel: Channel, stacked: Any,
                   prev: Any, ef: Any, kround, mask):
    """Shared per-round uplink crossing (lossy codecs only): both engines
    derive the codec key as ``fold_in(kround, 2)`` — index 1 is the
    strategies' derivation — and thread the EF residuals identically."""
    stacked, new_ef = placement.uplink(
        channel.codec, stacked, prev, ef, jax.random.fold_in(kround, 2),
        mask)
    return stacked, (new_ef if channel.error_feedback else ef)


def channel_extra(history: "History", channel: Channel, link,
                  model_bits: int, ul_payload: int) -> None:
    """Shared `History.extra["channel"]` record of a channel-carrying run
    (both engines): codec/link identity, per-payload bits and the run's
    cumulative bit totals (the §3b bits axes)."""
    history.extra["channel"] = {
        "codec": channel.codec.spec,
        "error_feedback": bool(channel.error_feedback),
        "link": link.name if link is not None else None,
        "model_bits": int(model_bits),
        "payload_bits": int(ul_payload),
        "dl_bits_total": int(sum(c.dl_bits for c in history.comm_bits)),
        "ul_bits_total": int(sum(c.ul_bits for c in history.comm_bits)),
    }


# ---------------------------------------------------------------------------
# superstep execution (DESIGN.md §3c): fuse eval_every rounds into one scan


def _mro_definer(cls: type, name: str) -> Optional[type]:
    """The class in ``cls``'s MRO that actually defines ``name``."""
    for c in cls.__mro__:
        if name in vars(c):
            return c
    return None


def superstep_support(strategy: Strategy,
                      sampler: Optional[ClientSampler],
                      hierarchy: Optional[Any] = None) -> tuple:
    """(ok, reason) — whether this run qualifies for the fused superstep.

    Strategy and sampler must declare the traceability contract; every
    registered codec's ``roundtrip`` is already a pure traced function, so
    a `Channel` never blocks fusion.  A subclass of a traceable strategy
    that overrides the eventful hooks (``aggregate``/``reweight``)
    WITHOUT re-implementing ``aggregate_traced`` would silently fuse with
    the parent's traced rule — detected here and routed to the eventful
    loop instead."""
    if not strategy.traceable:
        return False, (f"strategy {strategy.spec!r} is not traceable "
                       "(eventful per-round state)")
    cls = type(strategy)
    traced_at = _mro_definer(cls, "aggregate_traced")
    for name in ("aggregate", "reweight"):
        at = _mro_definer(cls, name)
        if at is not Strategy and not issubclass(traced_at, at):
            return False, (
                f"{cls.__name__} overrides {name}() below the class "
                f"defining aggregate_traced ({traced_at.__name__}); the "
                "traced path would silently diverge — override "
                "aggregate_traced too (or set traceable=False)")
    if sampler is not None and not sampler.traceable:
        return False, (f"sampler {type(sampler).__name__} does not "
                       "implement sample_traced")
    if hierarchy is not None:
        agg = hierarchy.edge_aggregator
        if not agg.traceable:
            return False, (f"edge aggregator {agg.spec!r} is not traceable "
                           "(host-side edge weighting, DESIGN.md §3f)")
    return True, ""


# compiled supersteps, shared across `run_federated` calls: key ->
# {scan length -> jitted superstep}.  The key captures everything the
# trace closes over (the cached update step object carries the
# loss_fn/FLConfig identity; strategy and sampler contribute their
# spec-level identities; the placement its mesh/schedule; `acc_fn` the
# fused chunk-end eval) — but NOT the client count: the traced round
# derives m from the data shapes, so the jit wrapper re-specializes per
# COHORT SHAPE on its own and one cache entry serves every population
# size (the paging engine's executable-reuse contract, DESIGN.md §3e).
# Bounded like the neighboring executable caches (`cached_update`,
# `_uplink_fn`): oldest config evicted past the cap, so sweep processes
# iterating many (scenario × algorithm × codec) configs don't pin
# executables forever.
_SUPERSTEP_FNS: Dict[tuple, Dict[int, Callable]] = {}
_SUPERSTEP_CACHE_MAX = 32


def _superstep_cache(placement: Placement, strategy: Strategy,
                     sampler: Optional[ClientSampler],
                     codec, error_feedback: bool, update_fn: Callable,
                     acc_fn: Callable, fault_cfg: Optional[Any] = None,
                     robust_spec: Optional[str] = None,
                     min_quorum: Optional[int] = None) -> Dict[int, Callable]:
    # fault/defense/quorum identity is part of the key: the cached jitted
    # superstep wraps the FIRST round_fn seen for a key, and the fault
    # injector/defense/quorum gate are traced INTO that round (§3g)
    key = (placement.cache_key(), type(strategy), strategy.spec,
           None if sampler is None else sampler.cache_key,
           codec, bool(error_feedback), update_fn, acc_fn,
           fault_cfg, robust_spec, min_quorum)
    cache = _SUPERSTEP_FNS.pop(key, None)   # re-insert: LRU, not FIFO
    if cache is None:
        while len(_SUPERSTEP_FNS) >= _SUPERSTEP_CACHE_MAX:
            _SUPERSTEP_FNS.pop(next(iter(_SUPERSTEP_FNS)))
        cache = {}
    _SUPERSTEP_FNS[key] = cache
    return cache


def _build_traced_round(strategy: Strategy, sampler: Optional[ClientSampler],
                        codec, error_feedback: bool, placement: Placement,
                        update_fn: Callable, fault_plan: Optional[Any] = None,
                        defense: Optional[Any] = None,
                        min_quorum: Optional[int] = None) -> Callable:
    """The fused round: (local update → sampler select → fault injection →
    codec uplink with error feedback → screening/robust defense →
    strategy aggregate → quorum gate) as one pure function

        round_fn((key, stacked, opt_state, ef), (x, y, n), consts)
            -> ((key', stacked', opt_state', ef'), (mask, crash, quarantine))

    with EXACTLY the eventful engine's key derivation — ``ksample`` split
    first (stochastic samplers only), then ``kround``; per-client batch
    keys are ``split(kround, m)``, the codec key ``fold_in(kround, 2)``
    (index 1 stays reserved for the strategies' derivation, index 3 for
    the fault injector) — so the fused run is bit-identical to the
    per-round loop.  The client count m comes from the traced data
    shapes, NOT from the builder: one round_fn (and so one cached
    superstep) serves every cohort size, which is what lets the paging
    engine (DESIGN.md §3e) reuse executables across populations.

    With ``fault_plan`` (DESIGN.md §3g) ``consts`` is the pair
    ``(strategy_consts, byz_row)`` — the static adversary row rides as a
    traced input so per-cohort rows never retrace.  Crash rolls the row
    back exactly like a sampler no-show; the other faults corrupt what
    the row TRANSMITS.  ``min_quorum`` snapshots the clients' own models
    before the uplink and discards the mixed result when too few rows
    participated (the round's uploads are wasted; the server state
    carries forward).  All three knobs off is byte-for-byte the
    pre-faults trace — the parity anchor."""
    tmix = TracedMix(placement)
    lossy = codec is not None and not codec.is_identity
    backend = placement.codec_backend
    faulted = fault_plan is not None

    def round_fn(carry, data, consts):
        if faulted:
            consts, byz_row = consts
        key, stacked, opt_state, ef = carry
        x, y, n = data
        m = x.shape[0]      # static under trace: the cohort shape
        ksample = None
        if sampler is not None and sampler.needs_key:
            key, ksample = jax.random.split(key)
        key, kround = jax.random.split(key)
        ckeys = jax.random.split(kround, m)
        prev, prev_opt = stacked, opt_state
        stacked, opt_state = update_fn(stacked, opt_state, x, y, n, ckeys)
        mask = None
        if sampler is not None:
            # all-True where the eventful sampler would return None: the
            # row-select below is then a bitwise identity.  Through the
            # placement's `select` hook (pure on both backends) so a
            # backend overriding rollback keeps working under fusion.
            mask = sampler.sample_traced(ksample, m)
            stacked = placement.select(mask, stacked, prev)
            opt_state = placement.select(mask, opt_state, prev_opt)
        crash = None
        if faulted:
            kfault = jax.random.fold_in(kround, 3)
            if fault_plan.value_faults:
                stacked = inject_values(fault_plan, byz_row, stacked, prev,
                                        kfault, rows=mask)
            crash = crash_mask(fault_plan, kfault, m)
            if crash is not None:
                # a crashed client never reports: row rollback, exactly a
                # sampler no-show
                stacked = placement.select(~crash, stacked, prev)
                opt_state = placement.select(~crash, opt_state, prev_opt)
        part = mask
        if crash is not None:
            part = ~crash if part is None else part & ~crash
        # quorum snapshot: the clients' own post-update models BEFORE the
        # uplink — on a skipped round each keeps what it computed
        clients = stacked if min_quorum is not None else None
        if lossy:
            new_stacked, new_ef = uplink_roundtrip(
                codec, stacked, prev, ef, jax.random.fold_in(kround, 2),
                part, backend=backend)
            stacked = new_stacked
            ef = new_ef if error_feedback else ef
        q = None
        if defense is not None:
            stacked, q = screen_and_defend(defense, stacked, prev)
            tmix.quarantine = q
        with jax.named_scope("aggregate"):
            stacked = strategy.aggregate_traced(consts, stacked, prev, tmix)
        tmix.quarantine = None
        if min_quorum is not None:
            count = (jnp.float32(m) if part is None
                     else jnp.sum(part.astype(jnp.float32)))
            ok = count >= jnp.float32(min_quorum)
            stacked = jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok, a, b), stacked, clients)
        return (key, stacked, opt_state, ef), (mask, crash, q)

    return round_fn


def _eval_rounds(rounds: int, eval_every: int):
    """The eventful engine's eval boundaries (``rnd % eval_every == 0 or
    rnd == rounds - 1``) as consecutive chunk ends: yields the round index
    each superstep runs up to (inclusive)."""
    rnd = 0
    while rnd < rounds:
        nxt = min(((rnd + eval_every - 1) // eval_every) * eval_every,
                  rounds - 1)
        yield rnd, nxt
        rnd = nxt + 1


def charge_round(history: "History", cost: CommCost, mask_np, m: int,
                 payload: int, link, system: Optional[SystemModel],
                 channel: Optional[Channel], t_accum: float,
                 assignment: Optional[np.ndarray] = None,
                 ul_bits_pc: Optional[np.ndarray] = None,
                 edge: Optional[Any] = None) -> float:
    """One round's comm/bits/clock accounting, SHARED by the eventful loop
    and the superstep replay so the two engines can't drift (like
    `init_run`/`init_channel` for the prologue).  ``mask_np`` is the
    HOST-side participation row (None or all-True = full cohort — the
    eventful sampler returns None there); returns the updated clock.
    ``assignment`` is the strategy's client→stream map (membership-aware
    broadcast charging, None = legacy cohort-slowest upper bound);
    ``ul_bits_pc`` the (m,) per-client uplink payload vector (rate-
    adaptive codecs; None = uniform ``payload`` per client); ``edge`` the
    hierarchy tier's `EdgeMeter` (DESIGN.md §3f) — the device→user hop's
    bits land in its own books every round and its time (slowest
    participating user's edge sub-round) is added to the clock whenever a
    ``system`` runs one."""
    history.comm.append(cost)
    n_part, participants = m, None
    if channel is not None or system is not None or edge is not None:
        # the round only waits for the clients that computed: H_|S| under
        # partial participation, not H_m
        if mask_np is not None and not mask_np.all():
            n_part = int(mask_np.sum())
            participants = np.where(mask_np)[0]
    if channel is not None:
        # downlink streams move the codec-compressed model (§3b)
        if ul_bits_pc is None:
            ul_bits = n_part * payload
        else:
            idx = participants if participants is not None else slice(None)
            ul_bits = int(np.sum(ul_bits_pc[idx]))
        history.comm_bits.append(ChannelCost(
            dl_bits=(cost.n_streams + cost.n_unicasts) * payload,
            ul_bits=ul_bits))
    if system is not None:
        if link is not None:
            ul = payload if ul_bits_pc is None else ul_bits_pc
            t_accum += (system.compute_time(n_part)
                        + link.max_uplink_time(ul, participants)
                        + round_downlink_time(link, cost, payload,
                                              participants, assignment))
        else:
            t_accum += system.round_time(n_part, n_streams=cost.n_streams,
                                         n_unicasts=cost.n_unicasts)
    if edge is not None:
        t_edge = edge.charge(mask_np)
        if system is not None:
            t_accum += t_edge
    return t_accum


@dataclass
class History:
    rounds: List[int] = field(default_factory=list)
    mean_acc: List[float] = field(default_factory=list)
    worst_acc: List[float] = field(default_factory=list)
    time: List[float] = field(default_factory=list)
    comm: List[CommCost] = field(default_factory=list)
    # bits-based sibling of `comm`, one entry per round — populated only
    # when the run carries a Channel (DESIGN.md §3b)
    comm_bits: List[ChannelCost] = field(default_factory=list)
    extras: Optional[StrategyExtras] = None
    # legacy mapping view, filled by the engine from `comm` + `extras`;
    # a real dict so pre-redesign callers that annotate it keep working
    extra: Dict[str, Any] = field(default_factory=dict)
    # populated when run_federated(keep_state=True): the final client-
    # stacked params / optimizer state (still device-resident)
    final_params: Any = None
    final_opt_state: Any = None


class NonFiniteEvalWarning(RuntimeWarning):
    """A recorded eval score was NaN/Inf — the run diverged."""


def record_eval(history: "History", rnd: int, mean_acc: float,
                worst_acc: float, t_accum: float) -> None:
    """Shared eval bookkeeping for every engine: appends one eval row and
    guards the scores — a NaN/Inf accuracy warns `NonFiniteEvalWarning`
    loudly (so diverged runs fail CI benches instead of silently charting
    garbage) and is booked under ``History.extra["nonfinite_evals"]``.
    Undefended NaN fault injection (DESIGN.md §3g) trips this; the
    screening defense keeps scores finite."""
    if not (np.isfinite(mean_acc) and np.isfinite(worst_acc)):
        warnings.warn(
            f"non-finite eval at round {rnd}: mean_acc={mean_acc}, "
            f"worst_acc={worst_acc} — the run diverged (NaN/Inf client "
            "updates reached aggregation; a robust_agg/screening defense "
            "would quarantine them, DESIGN.md §3g)",
            NonFiniteEvalWarning, stacklevel=2)
        history.extra["nonfinite_evals"] = (
            history.extra.get("nonfinite_evals", 0) + 1)
    history.rounds.append(rnd)
    history.mean_acc.append(mean_acc)
    history.worst_acc.append(worst_acc)
    history.time.append(t_accum)


def _run_superstep(strategy: Strategy, fed: FederatedData, *,
                   sampler: Optional[ClientSampler], fl: "FLConfig",
                   model_init: Optional[Callable], loss_fn: Callable,
                   acc_fn: Callable, system: Optional[SystemModel],
                   placement: Placement, channel: Optional[Channel],
                   keep_state: bool, seed: int,
                   hierarchy: Optional[Any] = None,
                   faults: Optional[Any] = None,
                   robust_agg: Optional[str] = None,
                   min_quorum: Optional[int] = None) -> "History":
    """Scan-compiled sync run (DESIGN.md §3c): Python re-enters only at
    eval boundaries; per-round participation masks come back as ONE
    stacked device->host transfer per superstep, the chunk-end eval runs
    INSIDE the compiled superstep (fused onto the end of the scan — no
    separate eval dispatch on the hot path), and the clock/CommCost/
    ChannelCost accounting is replayed host-side in the eventful engine's
    exact per-round order (bit-identical histories).  The fault injector,
    defense layer and quorum gate (DESIGN.md §3g) trace into the same
    scan; their per-round crash/quarantine rows ride the superstep outs
    next to the masks and are replayed into the `FaultMeter` here."""
    m = fed.m
    with jax.profiler.TraceAnnotation("fl.init"):
        key, update_fn, stacked, opt_state, data, ctx, state = init_run(
            strategy, fed, fl, model_init, loss_fn, acc_fn, placement, seed,
            # donation happens at the superstep boundary instead
            donate=False, hierarchy=hierarchy, system=system, faults=faults)
        plan = ctx.fault_plan
        defense = get_robust_aggregator(robust_agg)
        robust_spec = "none" if defense is None else str(robust_agg)
        meter = None
        if hierarchy is not None:
            from repro.fl.hierarchy import EdgeMeter
            meter = EdgeMeter(ctx.hierarchy_plan)
        fmeter = None
        if plan is not None or defense is not None or min_quorum is not None:
            fmeter = FaultMeter(plan, robust_spec, min_quorum)
        payload, link, model_bits, ef, channel = init_channel(
            channel, ctx, stacked, system, m)
        lossy = channel is not None and not channel.codec.is_identity
        # identity codecs trace no uplink: normalize so channel-less and
        # identity-channel runs share one compiled superstep
        codec = channel.codec if lossy else None
        ef_flag = channel.error_feedback if lossy else True
        consts = strategy.traced_state(state)
        if plan is not None:
            # the static adversary row rides as a traced const input (§3g)
            consts = (consts, jnp.asarray(plan.byz_row()))
        round_fn = _build_traced_round(strategy, sampler, codec, ef_flag,
                                       placement, update_fn, fault_plan=plan,
                                       defense=defense, min_quorum=min_quorum)
        cache = _superstep_cache(
            placement, strategy, sampler, codec, ef_flag, update_fn, acc_fn,
            fault_cfg=None if plan is None else plan.cfg,
            robust_spec=robust_spec, min_quorum=min_quorum)
        eval_fn = lambda st, ed: placement.eval_traced(acc_fn, st, ed[0],
                                                       ed[1])
        cost = strategy.comm(state)     # round-constant by the traceability
        history = History()             # contract (state never changes)
        assignment = strategy.membership(state)      # round-constant too
        ul_bits_pc = per_client_uplink_bits(channel, ctx, payload, m)
        t_accum = 0.0
        carry = (key, stacked, opt_state, ef if lossy else None)

    for rnd, nxt in _eval_rounds(fl.rounds, fl.eval_every):
        length = nxt - rnd + 1
        with jax.profiler.TraceAnnotation("fl.superstep"):
            carry, outs, accs = placement.run_supersteps(
                round_fn, carry, data, consts, length, cache=cache,
                eval_fn=eval_fn, eval_data=(fed.x_val, fed.y_val))
        masks, crashes, qs = outs
        with jax.profiler.TraceAnnotation("fl.readback"):
            # the chunk's blocking device->host reads: the masks only when
            # a clock, the bits axis or a meter consumes them, the scores
            masks_np = (np.asarray(masks)
                        if masks is not None
                        and (channel is not None or system is not None
                             or meter is not None or fmeter is not None)
                        else None)
            crashes_np = None if crashes is None else np.asarray(crashes)
            qs_np = None if qs is None else np.asarray(qs)
            mean_acc, worst_acc = reduce_scores(accs)
        with jax.profiler.TraceAnnotation("fl.replay"):
            for i in range(length):
                mrow = None if masks_np is None else masks_np[i]
                crow = None if crashes_np is None else crashes_np[i]
                eff = mrow
                if crow is not None:
                    eff = ~crow if eff is None else eff & ~crow
                n_eff = m if eff is None else int(eff.sum())
                ok = min_quorum is None or n_eff >= min_quorum
                # a quorum-skipped round moves no server model: no downlink
                # streams, no membership-aware broadcast — but the clients
                # DID compute and upload (eff mask → compute + uplink time
                # accrue)
                t_accum = charge_round(
                    history, cost if ok else CommCost(0, 0), eff, m,
                    payload, link, system, channel, t_accum,
                    assignment if ok else None, ul_bits_pc, meter)
                if fmeter is not None:
                    qrow = None if qs_np is None else qs_np[i]
                    rbits = qbits = 0
                    if channel is not None:
                        rbits = (n_eff * payload if ul_bits_pc is None
                                 else int(np.sum(ul_bits_pc[eff])
                                          if eff is not None
                                          else np.sum(ul_bits_pc)))
                        if qrow is not None:
                            qbits = int(np.sum(qrow <= 0)) * payload
                    fmeter.charge(crow, qrow, ok, rbits, qbits)
            record_eval(history, nxt, mean_acc, worst_acc, t_accum)

    with jax.profiler.TraceAnnotation("fl.finalize"):
        _, stacked, opt_state, _ = carry
        history = finalize_history(history, strategy, state, keep_state,
                                   stacked, opt_state)
        if meter is not None:
            history.extra["hierarchy"] = meter.extra()
        if fmeter is not None:
            history.extra["faults"] = fmeter.extra()
        if channel is not None:
            channel_extra(history, channel, link, model_bits, payload)
    return history


def run_federated(algorithm: Union[str, Strategy, None] = None,
                  fed: Optional[FederatedData] = None, *,
                  strategy: Optional[Strategy] = None,
                  sampler: Optional[ClientSampler] = None,
                  fl: Optional[FLConfig] = None,
                  model_init: Optional[Callable] = None,
                  loss_fn: Callable = lenet.loss_fn,
                  acc_fn: Callable = lenet.accuracy,
                  system: Optional[SystemModel] = None,
                  placement: Optional[Placement] = None,
                  channel: Union[str, Channel, None] = None,
                  keep_state: bool = False,
                  async_cfg: Optional[Any] = None,
                  superstep: Optional[bool] = None,
                  paging: Optional[Any] = None,
                  hierarchy: Optional[Any] = None,
                  faults: Optional[Any] = None,
                  robust_agg: Optional[str] = None,
                  min_quorum: Optional[int] = None,
                  seed: int = 0) -> History:
    """Run one strategy on one scenario; returns accuracy/time history.

    algorithm: a registry spec string (``"fedavg"``, ``"ucfl_k3"``, ...)
    or a `Strategy` instance; alternatively pass ``strategy=``.  ``sampler``
    selects per-round client participation (default: everyone).
    ``placement`` selects the client layout backend (default `HostVmap`,
    bit-identical to the pre-placement engine); ``keep_state=True``
    attaches the final stacked params / opt state to the History.
    ``channel`` (a `Channel` or codec spec string, DESIGN.md §3b) turns on
    bit-level payload accounting, uplink compression with error feedback
    and per-client link timing; ``Channel()``/None with the identity codec
    are bit-identical.  ``async_cfg`` (an `AsyncConfig`) switches to the
    event-driven buffered-async runtime (DESIGN.md §3a).  ``superstep``
    (DESIGN.md §3c) compiles ``eval_every`` consecutive rounds as one
    device-resident `lax.scan`: None (default) fuses exactly when
    strategy and sampler satisfy the traceability contract (bit-identical
    histories either way), False forces the eventful per-round loop, True
    raises if the configuration cannot fuse.  ``paging`` (a
    `PagingConfig`, DESIGN.md §3e) switches to the cohort paging engine:
    the full client population lives in a host-backed store and only one
    cohort is device-resident per superstep.  ``hierarchy`` (a
    `HierarchyConfig`, an int devices-per-user, or a fleet spec string —
    DESIGN.md §3f) nests an edge sub-round inside every round: each user
    aggregates its device fleet before the server sees it, both hops are
    charged, and the device→user hop's bits land in
    ``History.extra["hierarchy"]``.  ``faults`` (a `FaultConfig` or spec
    string like ``"crash:0.1,byz:0.25:sign_flip"``, DESIGN.md §3g)
    injects deterministic seeded client failures; ``robust_agg``
    (``none | clip:<c> | trimmed_mean:<f> | median | krum:<f>``) screens
    non-finite uploads and robustifies the aggregation against them;
    ``min_quorum`` skips aggregation on rounds where fewer clients
    participate (the server state carries forward).  All three default
    off and off is bit-identical to the pre-faults engine; the run's
    fault ledger lands in ``History.extra["faults"]``.
    """
    if min_quorum is not None:
        min_quorum = int(min_quorum)
        if min_quorum < 1:
            raise ValueError(f"min_quorum must be >= 1, got {min_quorum}")
    faults = resolve_faults(faults)     # validates the spec once, up front
    if hierarchy is not None:
        from repro.fl.hierarchy import resolve_hierarchy
        hierarchy = resolve_hierarchy(hierarchy)
    if async_cfg is not None:
        if sampler is not None:
            raise TypeError("the async runtime takes no ClientSampler — "
                            "the arrival buffer is the per-event cohort")
        if superstep:
            raise TypeError("superstep fusion is a synchronous-engine "
                            "feature; the async runtime is event-driven")
        from repro.fl.runtime import run_async
        return run_async(algorithm, fed, strategy=strategy,
                         async_cfg=async_cfg, fl=fl, model_init=model_init,
                         loss_fn=loss_fn, acc_fn=acc_fn, system=system,
                         placement=placement, channel=channel,
                         keep_state=keep_state, paging=paging,
                         hierarchy=hierarchy, faults=faults,
                         robust_agg=robust_agg, min_quorum=min_quorum,
                         seed=seed)
    if paging is not None:
        if hierarchy is not None:
            raise TypeError("the hierarchy tier does not compose with the "
                            "cohort paging engine yet (the store pages "
                            "flat client rows, not device fleets)")
        if superstep is False:
            raise TypeError("the paging engine runs fused supersteps only "
                            "(DESIGN.md §3e); superstep=False cannot page")
        from repro.fl.population import run_paged
        return run_paged(algorithm, fed, paging=paging, strategy=strategy,
                         sampler=sampler, fl=fl, model_init=model_init,
                         loss_fn=loss_fn, acc_fn=acc_fn, system=system,
                         placement=placement, channel=channel,
                         keep_state=keep_state, faults=faults,
                         robust_agg=robust_agg, min_quorum=min_quorum,
                         seed=seed)
    strategy = resolve_strategy(algorithm, strategy)
    if fed is None:
        raise TypeError("`fed` is required")
    fl = FLConfig() if fl is None else fl
    placement = resolve_placement(placement)
    channel = resolve_channel(channel)
    codec = channel.codec if channel is not None else None
    lossy = codec is not None and not codec.is_identity

    if superstep is None or superstep:
        ok, why = superstep_support(strategy, sampler, hierarchy=hierarchy)
        if not ok and superstep:
            raise ValueError(f"superstep=True but this run cannot fuse: "
                             f"{why}")
        if ok:
            return _run_superstep(strategy, fed, sampler=sampler, fl=fl,
                                  model_init=model_init, loss_fn=loss_fn,
                                  acc_fn=acc_fn, system=system,
                                  placement=placement, channel=channel,
                                  keep_state=keep_state,
                                  hierarchy=hierarchy, faults=faults,
                                  robust_agg=robust_agg,
                                  min_quorum=min_quorum, seed=seed)

    m = fed.m
    defense = get_robust_aggregator(robust_agg)
    # When no sampler can roll clients back and the strategy declares it
    # never reads `prev`, the update step may consume (donate) the old
    # stacked/opt buffers — peak memory drops from ~2× params+opt to ~1×.
    # A lossy codec reads `prev` too (the uplink transmits Δ = new − prev);
    # so do the fault injector and the screening defense (both work on
    # Δ = new − prev).  `min_quorum` alone stays donate-safe: its snapshot
    # is the post-update clients stack, never `prev`.
    donate = (sampler is None and not strategy.reads_prev and not lossy
              and faults is None and defense is None)
    key, vmapped_update, stacked, opt_state, (x, y, n), ctx, state = \
        init_run(strategy, fed, fl, model_init, loss_fn, acc_fn,
                 placement, seed, donate=donate, hierarchy=hierarchy,
                 system=system, faults=faults)
    plan = ctx.fault_plan
    robust_spec = "none" if defense is None else str(robust_agg)
    byz_row = None if plan is None else jnp.asarray(plan.byz_row())
    meter = None
    if hierarchy is not None:
        from repro.fl.hierarchy import EdgeMeter
        meter = EdgeMeter(ctx.hierarchy_plan)
    fmeter = None
    if plan is not None or defense is not None or min_quorum is not None:
        fmeter = FaultMeter(plan, robust_spec, min_quorum)

    payload, link, model_bits, ef, channel = init_channel(
        channel, ctx, stacked, system, m)
    ul_bits_pc = per_client_uplink_bits(channel, ctx, payload, m)

    history = History()
    t_accum = 0.0

    for rnd in range(fl.rounds):
        ksample = None
        if sampler is not None and sampler.needs_key:
            key, ksample = jax.random.split(key)
        key, kround = jax.random.split(key)
        ckeys = placement.place_keys(jax.random.split(kround, m))
        # donated buffers are dead after the update call: strategies that
        # declared reads_prev=False see prev=None
        prev, prev_opt = (None, None) if donate else (stacked, opt_state)
        stacked, opt_state = vmapped_update(stacked, opt_state, x, y, n,
                                            ckeys)

        mask = sampler.sample(rnd, m, ksample) if sampler is not None else None
        if mask is not None:
            # non-participants keep their pre-round model and optimizer
            stacked = placement.select(mask, stacked, prev)
            opt_state = placement.select(mask, opt_state, prev_opt)

        crash = None
        if plan is not None:
            # fault injection (DESIGN.md §3g): value faults corrupt what
            # the row transmits; crash rolls the row back like a no-show
            kfault = jax.random.fold_in(kround, 3)
            if plan.value_faults:
                stacked = inject_values(plan, byz_row, stacked, prev,
                                        kfault, rows=mask)
            crash = crash_mask(plan, kfault, m)
            if crash is not None:
                stacked = placement.select(~crash, stacked, prev)
                opt_state = placement.select(~crash, opt_state, prev_opt)
        part = mask
        if crash is not None:
            part = ~crash if part is None else part & ~crash
        # quorum snapshot: the clients' own post-update models BEFORE the
        # uplink — on a skipped round each keeps what it computed
        clients_snap = stacked if min_quorum is not None else None

        if lossy:
            # uplink channel crossing (DESIGN.md §3b): the server receives
            # the codec's decode(encode(Δ + residual))
            stacked, ef = channel_uplink(placement, channel, stacked, prev,
                                         ef, kround, part)

        q = None
        if defense is not None:
            # screening + robust aggregation (DESIGN.md §3g), before the
            # strategy's mixing — quarantined rows' deltas are zeroed and
            # their aggregation-weight columns renormalized away
            stacked, q = screen_and_defend(defense, stacked, prev)

        # ONE host sync per round at most (the mask pull), none when no
        # clock, bits axis or meter consumes it — n_part and the link-clock
        # participants both come from the same host-side array inside
        # `charge_round` (shared with the superstep replay).  The quorum
        # gate always needs the count, so it forces the pull.
        eff_np = (np.asarray(part)
                  if part is not None
                  and (channel is not None or system is not None
                       or meter is not None or fmeter is not None
                       or min_quorum is not None)
                  else None)
        n_eff = m if eff_np is None else int(eff_np.sum())
        ok = min_quorum is None or n_eff >= min_quorum
        if ok:
            # strategies get their own key derivation: kround's raw splits
            # are already consumed as the per-client minibatch keys
            ctx.rnd, ctx.key, ctx.participation = \
                rnd, jax.random.fold_in(kround, 1), part
            ctx.quarantine = q
            stacked, state = strategy.aggregate(state, stacked, prev, ctx)
            ctx.quarantine = None
        else:
            # below quorum: the mixed result never happens — every client
            # keeps its own pre-uplink model, the server state carries
            # forward, and the round's uploads are wasted
            stacked = clients_snap

        t_accum = charge_round(history,
                               strategy.comm(state) if ok else CommCost(0, 0),
                               eff_np, m, payload, link, system, channel,
                               t_accum,
                               strategy.membership(state) if ok else None,
                               ul_bits_pc, meter)
        if fmeter is not None:
            crow = None if crash is None else np.asarray(crash)
            qrow = None if q is None else np.asarray(q)
            rbits = qbits = 0
            if channel is not None:
                rbits = (n_eff * payload if ul_bits_pc is None else
                         int(np.sum(ul_bits_pc[eff_np])
                             if eff_np is not None else np.sum(ul_bits_pc)))
                if qrow is not None:
                    qbits = int(np.sum(qrow <= 0)) * payload
            fmeter.charge(crow, qrow, ok, rbits, qbits)

        if rnd % fl.eval_every == 0 or rnd == fl.rounds - 1:
            mean_acc, worst_acc = placement.evaluate(acc_fn, stacked, fed)
            record_eval(history, rnd, mean_acc, worst_acc, t_accum)

    history = finalize_history(history, strategy, state, keep_state,
                               stacked, opt_state)
    if meter is not None:
        history.extra["hierarchy"] = meter.extra()
    if fmeter is not None:
        history.extra["faults"] = fmeter.extra()
    if channel is not None:
        channel_extra(history, channel, link, model_bits, payload)
    return history
