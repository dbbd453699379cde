"""The plain reference of a federated job: what `correct` is judged by.

It follows the job the window drives -- the local SGD with momentum, the
uplink codec with error feedback, the strategy's set-up and aggregation,
and the chunk-end eval -- from the same seed and data, with the model of
the configuration's own reference file (`bench/configs/<config>_ref.py`,
which defines ``init(config, key)``, ``loss(config, params, x, y, cast)``
and ``score(config, params, x, y, cast)``).  It imports nothing of the
program and takes nothing the program made: it makes the initial weights
again from the seed, draws the minibatches and the codec noise from the
keys of the engine's documented derivation, and computes the strategy's
similarity statistics, Eq. 6 mixing matrix and k-means stream plan itself,
in float32 at the highest precision (the small host-side algebra in
float64).

Parameters and optimizer state are stored in the dtypes the configuration
states (``param_dtype``; momentum in it where ``opt_state_dtype`` is
"param"), as the program stores them: an update smaller than half a unit
in the last place of a bfloat16 parameter is lost in both.  ``cast``
rounds every stored value and every operand of a contraction: the
identity for the reference, a lower precision for the control.  ``fault``
plants one fault in the reference put in the program's place.
"""
from __future__ import annotations

import functools
import json
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("unchanged", "half_batch", "no_mix")
_PLANS: dict = {}       # the last plan, for the faults of the same seed
tmap = jax.tree_util.tree_map


def identity(a):
    return a


@functools.lru_cache(maxsize=None)
def rounding(dtype: str) -> Callable:
    """``cast`` that computes in ``dtype``: round to it and back to f32."""
    dt = jnp.dtype(dtype)
    return lambda a: a.astype(dt).astype(jnp.float32)


def storing(shapes) -> Callable:
    """Keep a model's values as its leaves' dtypes (``shapes``, as
    `jax.eval_shape` gives them) hold them."""
    dts = [l.dtype for l in jax.tree_util.tree_leaves(shapes)]

    def store(tree):
        leaves, tdef = jax.tree_util.tree_flatten(tree)
        return jax.tree_util.tree_unflatten(tdef, [
            a if dt == jnp.float32 else a.astype(dt).astype(jnp.float32)
            for a, dt in zip(leaves, dts)])
    return store


def flat(tree) -> jnp.ndarray:
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                            for l in jax.tree_util.tree_leaves(tree)])


class Bound:
    """The reference model of one configuration, its functions jitted
    once per process."""

    def __init__(self, ref, config: dict, fl: dict, cast: Callable,
                 half: bool):
        self.ref, self.config, self.fl, self.cast = ref, config, fl, cast
        self.vmap = bool(getattr(ref, "VMAP_CLIENTS", False))
        self.store = storing(jax.eval_shape(
            lambda k: ref.init(config, k), jax.random.PRNGKey(0)))
        self.store_mu = (self.store if config.get("opt_state_dtype")
                         == "param" else identity)
        loss = functools.partial(ref.loss, config)
        score = functools.partial(ref.score, config)
        one = functools.partial(_local_update, loss, fl, cast, self.store,
                                self.store_mu, half)
        sc = lambda p, xv, yv: score(p, xv, yv, cast)
        if self.vmap:
            self.update = jax.jit(jax.vmap(one))
            self.score = jax.jit(jax.vmap(sc))
        else:
            self.update1, self.score1 = jax.jit(one), jax.jit(sc)
        self.stats1 = getattr(ref, "client_stats", None)
        if self.stats1 is None:
            self.stats1 = jax.jit(functools.partial(_plain_stats, loss, cast),
                                  static_argnums=(3, 4))
        else:
            self.stats1 = functools.partial(self.stats1, config)

    def clients_update(self, models, mu, x, y, n, keys):
        """Every client's local update.  Clients are one stacked pytree
        where the model is small (vmapped), else a list of pytrees."""
        if self.vmap:
            return self.update(models, mu, x, y, n, keys)
        for i in range(len(models)):
            models[i], mu[i] = self.update1(models[i], mu[i], x[i], y[i],
                                            n[i], keys[i])
        return models, mu

    def clients_score(self, models, xv, yv) -> np.ndarray:
        if self.vmap:
            return np.asarray(self.score(models, xv, yv), np.float64)
        return np.asarray([float(self.score1(p, xv[i], yv[i]))
                           for i, p in enumerate(models)], np.float64)

    def stats(self, p0, xi, yi, bs, kb):
        if getattr(self.ref, "client_stats", None) is not None:
            return self.stats1(p0, xi, yi, bs, kb, self.cast)
        return self.stats1(p0, xi, yi, bs, kb)


@functools.lru_cache(maxsize=16)
def _bound(ref, config_json: str, fl_json: str, cast, half) -> Bound:
    return Bound(ref, json.loads(config_json), json.loads(fl_json), cast,
                 half)


def _local_update(loss, fl, cast, store, store_mu, half, p, mu, xi, yi, ni,
                  key):
    """The client's local SGD with momentum; minibatch rows as the engine
    draws them: ``randint(k, (B,), 0, 2**30) % max(n_i, 1) % slots``."""
    n_slots = xi.shape[0]
    keys = jax.random.split(key, fl["local_steps"])
    for t in range(fl["local_steps"]):
        idx = jax.random.randint(keys[t], (fl["batch_size"],), 0, 1 << 30) \
            % jnp.maximum(ni.astype(jnp.int32), 1)
        idx = idx % n_slots
        if half:                    # fault: half the batch, mean of the rest
            idx = idx[:fl["batch_size"] // 2]
        g = jax.grad(loss)(p, xi[idx], yi[idx], cast)
        mu = store_mu(tmap(lambda a, b: cast(fl["momentum"] * a + b), mu, g))
        p = store(tmap(lambda a, b: cast(a - fl["lr"] * b), p, mu))
    return p, mu


def _plain_stats(loss, cast, p0, xi, yi, bs, kb):
    g = lambda xx, yy: flat(jax.grad(loss)(p0, xx, yy, cast))
    full = g(xi, yi)
    dev = [jnp.sum(jnp.square(g(xi[j * bs:(j + 1) * bs],
                                yi[j * bs:(j + 1) * bs]) - full))
           for j in range(kb)]
    return full, jnp.mean(jnp.stack(dev))


# ---------------------------------------------------------------------------
# the strategy's plan


@jax.jit
def _sqdist(a, b):
    return jnp.sum(jnp.square(a - b))


@jax.jit
def _sqdist_rows(g):
    return jax.lax.map(lambda gi: jnp.sum(jnp.square(g - gi), axis=1), g)


def plan(model: Bound, strategy: str, fed, p0, seed: int,
         sigma_batches: int) -> tuple:
    """(centroids (k, m), assignment (m,)) of the strategy, in float64."""
    n = np.asarray(fed.n, np.float64)
    m = n.shape[0]
    if strategy == "fedavg":
        return (n / n.sum())[None, :], np.zeros(m, np.int64)
    if not strategy.startswith("ucfl_k"):
        raise ValueError(f"the reference has no strategy {strategy!r}")
    k = int(strategy[len("ucfl_k"):])
    bs = fed.x.shape[1] // sigma_batches
    grads, sig2 = [], []
    for i in range(m):              # Eq. 7 on the padded data set
        g, s2 = model.stats(p0, fed.x[i], fed.y[i], bs, sigma_batches)
        grads.append(g)
        sig2.append(float(s2))
    # direct differences, no Gram-matrix cancellation; pair by pair where
    # each gradient is large
    if m > 16:
        delta = np.asarray(_sqdist_rows(jnp.stack(grads)), np.float64)
    else:
        delta = np.zeros((m, m))
        for i in range(m):
            for j in range(i + 1, m):
                delta[i, j] = delta[j, i] = float(_sqdist(grads[i],
                                                          grads[j]))
    del grads
    w = mixing_matrix(delta, np.asarray(sig2), n)
    first = int(jax.random.randint(jax.random.PRNGKey(seed + 1), (), 0, m))
    return kmeans(w, k, first)


def mixing_matrix(delta, sigma2, n):
    """Paper Eq. 6: w_ij ~ (n_j / n_i) exp(-delta_ij / (2 sigma_i sigma_j))."""
    sigma = np.sqrt(np.maximum(sigma2, 1e-12))
    logits = np.log(n)[None, :] - delta / (2.0 * sigma[:, None]
                                           * sigma[None, :])
    logits -= logits.max(axis=1, keepdims=True)
    w = np.exp(logits)
    return w / w.sum(axis=1, keepdims=True)


def kmeans(rows, k, first, n_iter=50):
    """Paper §III-B's stream reduction: Lloyd's algorithm on the rows' off-
    diagonal collaboration profile, farthest-point seeding from client
    ``first``; centroids re-fit on the original rows, row-normalised."""
    m = rows.shape[0]
    k = min(k, m)
    x = rows * (1.0 - np.eye(m))
    x = x / np.maximum(x.sum(axis=1, keepdims=True), 1e-9)
    sq = lambda a, b: ((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
                       - 2.0 * a @ b.T)
    cents = [x[first]]
    for _ in range(1, k):
        cents.append(x[np.argmax(np.min(sq(x, np.stack(cents)), axis=1))])
    cents = np.stack(cents)
    for _ in range(n_iter):
        assign = np.argmin(sq(x, cents), axis=1)
        oh = np.eye(k)[assign]
        cnt = oh.sum(0)
        new = (oh.T @ x) / np.maximum(cnt, 1.0)[:, None]
        cents = np.where((cnt > 0)[:, None], new, cents)
    assign = np.argmin(sq(x, cents), axis=1)
    oh = np.eye(k)[assign]
    c = (oh.T @ rows) / np.maximum(oh.sum(0), 1.0)[:, None]
    return c / np.maximum(c.sum(axis=1, keepdims=True), 1e-9), assign


# ---------------------------------------------------------------------------
# the rounds


def _qsgd(v, key, bits, cast):
    """decode(encode(v)) per client row of the flat (m, D) view, the leaves
    in pytree order; stochastic rounding ``floor(v / scale + u)``."""
    leaves, tdef = jax.tree_util.tree_flatten(v)
    m = leaves[0].shape[0]
    x = jnp.concatenate([l.reshape(m, -1) for l in leaves], axis=1)
    noise = jax.random.uniform(key, x.shape, jnp.float32)
    s = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) * (1.0 / s)
    inv = jnp.where(scale > 0.0, 1.0 / scale, 0.0)
    dec = cast(jnp.clip(jnp.floor(x * inv + noise), -s, s) * scale)
    out, off = [], 0
    for l in leaves:
        size = int(np.prod(l.shape[1:]))
        out.append(dec[:, off:off + size].reshape(l.shape))
        off += size
    return jax.tree_util.tree_unflatten(tdef, out)


def _mix(models, cents, assign, cast, store):
    """theta_i <- sum_j C[a(i), j] theta_j, in float32 at the highest
    precision (stacked clients), or as weighted sums over a list."""
    hi = jax.lax.Precision.HIGHEST
    if not isinstance(models, list):
        c, a = jnp.asarray(cents, jnp.float32), jnp.asarray(assign)
        return store(tmap(lambda l: cast(jnp.tensordot(
            cast(c), cast(l), axes=(1, 0), precision=hi)[a]), models))
    streams = []
    for row in np.asarray(cents, np.float32):
        acc = tmap(lambda l: jnp.zeros(l.shape, jnp.float32), models[0])
        for w, p in zip(row, models):
            acc = tmap(lambda a_, b_: a_ + cast(jnp.float32(w)) * cast(b_),
                       acc, p)
        streams.append(store(tmap(cast, acc)))
    return [streams[int(a)] for a in assign]


def norms(models, base=None) -> np.ndarray:
    """(clients, leaves) norms of each client's leaves, minus ``base``."""
    if isinstance(models, list):
        return np.concatenate([norms(tmap(lambda a: a[None], p), base)
                               for p in models])
    cols = []
    base_leaves = None if base is None else jax.tree_util.tree_leaves(base)
    for i, l in enumerate(jax.tree_util.tree_leaves(models)):
        d = l.astype(jnp.float32)
        if base_leaves is not None:
            d = d - base_leaves[i].astype(jnp.float32)[None]
        cols.append(jnp.sqrt(jnp.sum(jnp.square(d.reshape(d.shape[0], -1)),
                                     axis=1)))
    return np.asarray(jnp.stack(cols, axis=1), np.float64)


def leaf_changes(models, base) -> list:
    """Each leaf's change from ``base``: (clients, size) on the host."""
    if isinstance(models, list):
        parts = [leaf_changes(tmap(lambda a: a[None], p), base) for p in models]
        return [np.concatenate(c) for c in zip(*parts)]
    return [np.asarray((l.astype(jnp.float32) - b.astype(jnp.float32)[None])
                       .reshape(l.shape[0], -1))
            for l, b in zip(jax.tree_util.tree_leaves(models),
                            jax.tree_util.tree_leaves(base))]


def run(ref, config: dict, fl: dict, mix: dict, fed, run_seed: int, *,
        cast: Callable = identity, fault: Optional[str] = None,
        changes: bool = False) -> dict:
    """Follow the first ``1 + eval_every`` rounds of the job on the device
    the reference file names (``DEVICE``: "cpu" where the chip's compiler
    cannot take the model's float32 gradients at the highest precision).
    Returns the readings the program's are compared with: momentum and
    change norms after round 0, change norms after the last round, the
    eval scores (m,) at rounds 0 and ``eval_every``, the plan's
    assignment, and with ``changes`` each leaf's change after round 0."""
    where = getattr(ref, "DEVICE", None)
    if where is None:
        return _run(ref, config, fl, mix, fed, run_seed, cast, fault,
                    id(fed), changes)
    dev = jax.devices(where)[0]
    with jax.default_device(dev):
        return _run(ref, config, fl, mix, jax.device_put(fed, dev), run_seed,
                    cast, fault, id(fed), changes)


def _run(ref, config, fl, mix, fed, run_seed, cast, fault, fed_id,
         keep_changes) -> dict:
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    model = _bound(ref, json.dumps(config, sort_keys=True),
                   json.dumps(fl, sort_keys=True), cast,
                   fault == "half_batch")
    m = fed.x.shape[0]
    every = int(mix["eval_every"])
    rounds = 1 + every
    key = jax.random.PRNGKey(run_seed)
    key, kinit = jax.random.split(key)
    p0 = model.store(tmap(lambda a: cast(a.astype(jnp.float32)),
                          ref.init(config, kinit)))
    pkey = (fed_id, run_seed, mix["strategy"], cast)
    if pkey not in _PLANS:          # a fault changes the rounds, not the plan
        if len(_PLANS) > 4:
            _PLANS.clear()
        _PLANS[pkey] = plan(model, mix["strategy"], fed, p0, run_seed,
                            int(mix.get("sigma_batches", 5)))
    cents, assign = _PLANS[pkey]
    if model.vmap:
        stack = tmap(lambda a: jnp.broadcast_to(a[None], (m,) + a.shape), p0)
        mu = tmap(jnp.zeros_like, stack)
    else:
        stack = [p0] * m
        mu = [tmap(jnp.zeros_like, p0) for _ in range(m)]
    codec = mix.get("codec") or "identity"
    if codec != "identity" and not (codec.startswith("qsgd:")
                                    and model.vmap):
        raise ValueError(f"the reference has no codec {codec!r} here")
    ef = tmap(jnp.zeros_like, stack) if codec != "identity" else None
    out = {"plan_assignment": np.asarray(assign).tolist(), "scores": {}}
    for rnd in range(rounds):
        key, kround = jax.random.split(key)
        ckeys = jax.random.split(kround, m)
        prev = stack if ef is not None else None
        if fault != "unchanged":
            stack, mu = model.clients_update(stack, mu, fed.x, fed.y, fed.n,
                                             ckeys)
        if ef is not None:
            v = tmap(lambda a, b, e: a - b + e, stack, prev, ef)
            dec = _qsgd(v, jax.random.fold_in(kround, 2), int(codec[5:]),
                        cast)
            ef = tmap(lambda a, b: cast(a - b), v, dec)
            stack = model.store(tmap(lambda a, b: cast(a + b), prev, dec))
        del prev
        if fault not in ("no_mix", "unchanged"):
            stack = _mix(stack, cents, assign, cast, model.store)
        if rnd == 0:
            out["mom_norms"] = norms(mu)
            out["change_norms"] = norms(stack, p0)
            if keep_changes:
                out["change"] = leaf_changes(stack, p0)
        if rnd % every == 0 or rnd == rounds - 1:
            out["scores"][rnd] = model.clients_score(stack, fed.x_val,
                                                     fed.y_val)
    out["change_norms_last"] = norms(stack, p0)
    return out
