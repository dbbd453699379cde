"""The one traffic generator: a mix file's ``data`` block -> FederatedData.

A traffic mix (``bench/traffic/<name>.json``) is data: the federated job
(strategy, codec, rounds, batch) and, under ``data``, the clients' data
set.  ``data["kind"]`` names one of the generators below.  Both are copies
of the repository's own generators, kept here so that no change to the
program can move the yardstick, and vectorised so that one jitted call on
the device makes a seed's data.

  covariate_shift  the paper's §IV-A.2 protocol (copy of
                   `repro.data.federated.scenario_covariate_shift` over
                   `repro.data.synthetic.synthetic_emnist`): EMNIST-like
                   28x28 images of 47 classes, a Dirichlet(alpha) label
                   split over m clients, and client i's images rotated by
                   90 degrees times (i mod 4).
  markov_lm        `repro.launch.train.lm_federated_data`: token sequences
                   from a noisy order-2 Markov rule, one rule per concept
                   group, clients assigned to groups round-robin.

Every seed gets the same shapes and the same client sizes, so one set of
compiled programs serves every seed.  For the image protocol the
Dirichlet split is drawn once, from the protocol's own fixed numpy seed,
over a fixed label draw; a run's seed then relabels the classes, reorders
the clients, and draws fresh prototypes, deformations and noise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.federated import FederatedData


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one use of a run's seed (any non-negative int)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make(data: dict, model: dict, seed: int) -> FederatedData:
    """The clients' data for one run; ``model`` is the configuration's
    file, whose vocabulary the token generator draws from."""
    kind = data["kind"]
    if kind == "covariate_shift":
        return covariate_shift(data, seed)
    if kind == "markov_lm":
        return markov_lm(data, int(model["vocab_size"]), seed)
    raise ValueError(f"unknown traffic data kind {kind!r}")


# ---------------------------------------------------------------------------
# images (copy of repro.data.synthetic, the EMNIST-like stand-in)


def _smooth_noise(key, n, size, channels, cutoff: int = 6):
    """Low-frequency random images via a truncated 2D Fourier basis."""
    kr, ki = jax.random.split(key)
    coef = (jax.random.normal(kr, (n, channels, cutoff, cutoff)) +
            1j * jax.random.normal(ki, (n, channels, cutoff, cutoff)))
    full = jnp.zeros((n, channels, size, size), jnp.complex64)
    full = full.at[:, :, :cutoff, :cutoff].set(coef)
    img = jnp.fft.ifft2(full).real
    img = img / (jnp.std(img, axis=(-2, -1), keepdims=True) + 1e-6)
    return jnp.transpose(img, (0, 2, 3, 1))      # NHWC


def _prototypes(key, n_classes, size, channels, separation,
                orientation_scale=1.5):
    """Shared base + class parts + a horizontal ramp (orientation marker)."""
    kb, kc = jax.random.split(key)
    base = _smooth_noise(kb, 1, size, channels)
    uniq = _smooth_noise(kc, n_classes, size, channels)
    ramp = jnp.broadcast_to(jnp.linspace(-1.0, 1.0, size)[None, :, None],
                            (size, size, channels))
    return base + separation * uniq + orientation_scale * ramp[None]


def _dirichlet_split(rng, labels, m, alpha, n_classes):
    """Class-wise proportional split: client weights ~ Dir(alpha) per class."""
    idx_by_class = [np.where(labels == c)[0] for c in range(n_classes)]
    client_idx = [[] for _ in range(m)]
    for idxs in idx_by_class:
        rng.shuffle(idxs)
        w = rng.dirichlet([alpha] * m)
        cuts = (np.cumsum(w) * len(idxs)).astype(int)[:-1]
        for i, part in enumerate(np.split(idxs, cuts)):
            client_idx[i].extend(part.tolist())
    for ci in client_idx:
        rng.shuffle(ci)
    return client_idx


def _client_rows(client_idx, val_frac):
    """(train rows (m, n_max), train sizes (m,), val rows (m, n_val)):
    `_stack_clients`' rule, with index arrays in place of the images."""
    sizes = [max(len(ci), 12) for ci in client_idx]
    n_val = max(4, int(min(sizes) * val_frac))
    n_train = [max(s - n_val, 8) for s in sizes]
    n_max = max(n_train)
    tr_rows, va_rows = [], []
    for ci, nt in zip(client_idx, n_train):
        ci = np.asarray(ci if len(ci) >= 12 else
                        np.resize(np.asarray(ci, int), 12), int)
        tr, va = ci[:nt], ci[nt:nt + n_val]
        if len(va) < n_val:
            va = np.resize(ci, n_val)
        tr_rows.append(np.resize(tr, n_max))    # repeat to n_max
        va_rows.append(va)
    return np.stack(tr_rows), np.asarray(n_train), np.stack(va_rows)


def covariate_shift(data: dict, seed: int) -> FederatedData:
    n, m, k = int(data["n"]), int(data["m"]), int(data["n_classes"])
    groups = int(data["rotation_groups"])
    if m % groups:
        raise ValueError(f"m={m} is not a multiple of {groups} groups")
    base = np.random.default_rng(int(data["split_seed"]))
    labels0 = base.integers(0, k, n)
    tr, n_tr, va = _client_rows(
        _dirichlet_split(base, labels0, m, float(data["alpha"]), k),
        float(data["val_frac"]))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0]))
    relabel = rng.permutation(k)
    order = rng.permutation(m)
    x, y, xv, yv = _images(seed_key(seed, 1), jnp.asarray(relabel[labels0]),
                           jnp.asarray(tr[order]), jnp.asarray(va[order]),
                           size=int(data["image_size"]), n_classes=k,
                           groups=groups)
    return FederatedData(x, y, jnp.asarray(n_tr[order], jnp.float32), xv, yv,
                         jnp.asarray(np.arange(m) % groups, jnp.int32))


@partial(jax.jit, static_argnames=("size", "n_classes", "groups"))
def _images(key, labels, tr, va, *, size, n_classes, groups):
    kp, ks = jax.random.split(key)
    protos = _prototypes(kp, n_classes, size, 1, separation=1.2)
    kd, kn = jax.random.split(ks)
    n = labels.shape[0]
    x = (protos[labels] + 0.5 * _smooth_noise(kd, n, size, 1)
         + 0.4 * jax.random.normal(kn, (n, size, size, 1)))

    def rotated(rows):
        # client i (row i) is turned by (i mod groups) quarter turns
        imgs = x[rows]                                    # (m, r, H, W, 1)
        m = rows.shape[0]
        per = imgs.reshape((m // groups, groups) + imgs.shape[1:])
        turned = [jnp.rot90(per[:, g], k=g, axes=(-3, -2))
                  for g in range(groups)]
        return jnp.stack(turned, axis=1).reshape(imgs.shape)

    return rotated(tr), labels[tr], rotated(va), labels[va]


# ---------------------------------------------------------------------------
# tokens (copy of repro.data.synthetic.synthetic_lm_tokens, vectorised)


def _markov_tokens(key, rule, batch, seq_len, vocab):
    """A noisy deterministic function of the previous len(rule) tokens."""
    k0, kn, kr = jax.random.split(key, 3)
    order = rule.shape[0]
    start = jax.random.randint(k0, (batch, order), 0, vocab)
    noise = jax.random.bernoulli(kn, 0.1, (batch, seq_len))
    rand = jax.random.randint(kr, (batch, seq_len), 0, vocab)

    def step(carry, t):
        nxt = (jnp.sum(carry * rule[None, :], axis=1) + 17) % vocab
        nxt = jnp.where(noise[:, t], rand[:, t], nxt)
        carry = jnp.concatenate([carry[:, 1:], nxt[:, None]], axis=1)
        return carry, nxt

    _, toks = jax.lax.scan(step, start, jnp.arange(seq_len))
    return jnp.transpose(toks, (1, 0)).astype(jnp.int32)


@partial(jax.jit, static_argnames=("m", "pool", "n_val", "seq", "vocab",
                                   "groups", "order"))
def _lm_tokens(key, *, m, pool, n_val, seq, vocab, groups, order):
    kr, kc = jax.random.split(key)
    rules = jax.random.randint(kr, (groups, order), 1, vocab - 1)
    client_rule = rules[jnp.arange(m) % groups]
    keys = jax.random.split(kc, m)
    train = jax.vmap(lambda k, r: _markov_tokens(
        jax.random.fold_in(k, 0), r, pool, seq, vocab))(keys, client_rule)
    val = jax.vmap(lambda k, r: _markov_tokens(
        jax.random.fold_in(k, 1), r, n_val, seq, vocab))(keys, client_rule)
    return train, val


def markov_lm(data: dict, vocab: int, seed: int) -> FederatedData:
    m, pool, n_val = int(data["m"]), int(data["pool"]), int(data["n_val"])
    groups = int(data["concept_groups"])
    x, xv = _lm_tokens(seed_key(seed, 1), m=m, pool=pool, n_val=n_val,
                       seq=int(data["seq"]), vocab=vocab,
                       groups=groups, order=int(data["markov_order"]))
    return FederatedData(
        x=x, y=jnp.zeros((m, pool), jnp.int32),
        n=jnp.full((m,), float(pool), jnp.float32),
        x_val=xv, y_val=jnp.zeros((m, n_val), jnp.int32),
        group=jnp.asarray(np.arange(m) % groups, jnp.int32))
