"""Plain float32 Mamba-2: the reference beside `mamba2-780m.json`.

Written from arXiv:2405.21060 in straightforward `jax.numpy` at the
highest matmul precision, and importing nothing of the program.  Each
block is pre-norm and residual: RMSNorm, then the Mamba-2 mixer -- one
input projection to (z, xBC, dt); a causal depthwise convolution of width
d_conv with SiLU over xBC; dt = softplus(dt + dt_bias); A = -exp(A_log);
the state-space model in its quadratic ("attention") dual form over the
whole sequence,

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < r <= t} dt_r A) dt_s x_s
          + D x_t,

a gated RMSNorm of y by SiLU(z), and the output projection.  The head is
tied to the embedding; the loss is next-token cross entropy.  It does not
chunk: the program's chunked scan must agree with this form.

Departures from the paper, each as the configuration states them: norm
scales are stored as (1 + scale) with scale starting at 0, and both norms
use epsilon 1e-6.

`init` is the benchmark's own weight maker, in the parameter tree the
program's scanned model reads (``scan_layers`` stacked over depth).
``cast`` is applied to every operand of a contraction and to every
stored value: the identity for the reference, a lower precision for the
control.  The model is large, so clients are taken one at a time, and a
block's activations are recomputed in the backward pass.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
VMAP_CLIENTS = False
# the control computes one precision below the bfloat16 the configuration
# states
CONTROL_DTYPE = "float8_e4m3fn"
EPS = 1e-6


def rows(c: dict) -> int:
    """Embedding rows: the tokenizer's ``vocab_size`` padded up to a
    multiple of ``pad_vocab_size_multiple``, as the checkpoint holds it."""
    pad = int(c.get("pad_vocab_size_multiple", 1))
    return -(-int(c["vocab_size"]) // pad) * pad


def dims(c: dict):
    d_inner = c["expand"] * c["d_model"]
    heads = d_inner // c["headdim"]
    conv_dim = d_inner + 2 * c["ngroups"] * c["d_state"]
    d_proj = 2 * d_inner + 2 * c["ngroups"] * c["d_state"] + heads
    return d_inner, heads, conv_dim, d_proj


def init(config: dict, key):
    """Random weights from the seed, in the configuration's param dtype."""
    c = config
    dt = jnp.dtype(c["param_dtype"])
    d, L, V = c["d_model"], c["n_layer"], rows(c)
    d_inner, nh, conv_dim, d_proj = dims(c)
    ks = jax.random.split(key, 6)
    nrm = lambda k, shape, fan: (jax.random.normal(k, shape, jnp.float32)
                                 / math.sqrt(fan))
    u = jax.random.uniform(ks[3], (L, nh))
    dt0 = jnp.exp(u * (math.log(c["dt_max"]) - math.log(c["dt_min"]))
                  + math.log(c["dt_min"]))
    layer = {
        "norm1": {"scale": jnp.zeros((L, d), dt)},
        "ssm": {
            "in_proj": nrm(ks[0], (L, d, d_proj), d).astype(dt),
            "conv_w": nrm(ks[1], (L, c["d_conv"], conv_dim),
                          c["d_conv"]).astype(dt),
            "conv_b": jnp.zeros((L, conv_dim), dt),
            "A_log": jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, nh)),
                                      (L, nh)).astype(jnp.float32),
            "D": jnp.ones((L, nh), jnp.float32),
            "dt_bias": jnp.log(jnp.expm1(dt0)).astype(jnp.float32),
            "norm_scale": jnp.zeros((L, d_inner), dt),
            "out_proj": nrm(ks[2], (L, d_inner, d), d_inner).astype(dt),
        },
    }
    return {"embed": nrm(ks[4], (V, d), d).astype(dt),
            "final_norm": {"scale": jnp.zeros((d,), dt)},
            "prefix_layers": [], "scan_layers": (layer,)}


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + scale)


def _mm(a, b, cast):
    return cast(jnp.matmul(cast(a), cast(b), precision=HI))


def _block(p, x, c, cast):
    """One Mamba-2 mixer on one sequence x (S, d)."""
    d_inner, nh, conv_dim, _ = dims(c)
    n, hd, K = c["d_state"], c["headdim"], c["d_conv"]
    S = x.shape[0]
    proj = _mm(x, p["in_proj"], cast)
    z = proj[:, :d_inner]
    xbc = proj[:, d_inner:d_inner + conv_dim]
    dt_raw = proj[:, d_inner + conv_dim:]
    xpad = jnp.concatenate([jnp.zeros((K - 1, conv_dim), x.dtype), xbc])
    conv = sum(xpad[i:i + S] * p["conv_w"][i] for i in range(K))
    xbc = cast(jax.nn.silu(conv + p["conv_b"]))
    xs = xbc[:, :d_inner].reshape(S, nh, hd)
    B = xbc[:, d_inner:d_inner + n]          # one group: (S, n)
    C = xbc[:, d_inner + n:]
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])              # (S, nh)
    A = -jnp.exp(p["A_log"])
    cs = jnp.cumsum(dt * A, axis=0)                          # (S, nh)
    seg = cs.T[:, :, None] - cs.T[:, None, :]                # (nh, t, s)
    causal = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    gram = _mm(C, B.T, cast)                                 # (t, s)
    w = cast(gram[None] * decay)                             # (nh, t, s)
    xdt = cast(xs * dt[:, :, None])                          # (s, nh, hd)
    y = cast(jnp.einsum("hts,shp->thp", w, xdt, precision=HI))
    y = y + p["D"][None, :, None] * xs
    y = y.reshape(S, d_inner) * jax.nn.silu(z)
    y = cast(_rmsnorm(y, p["norm_scale"]))
    return _mm(y, p["out_proj"], cast)


def _hidden(params, tokens, c, cast):
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(h, p):
        normed = cast(_rmsnorm(h, p["norm1"]["scale"]))
        return cast(h + _block(p["ssm"], normed, c, cast)), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["scan_layers"][0])
    return cast(_rmsnorm(x, params["final_norm"]["scale"]))


def _seq_nll(params, tokens, c, cast):
    """Mean next-token cross entropy of one sequence (S,)."""
    h = _hidden(params, tokens, c, cast)[:-1]
    logits = _mm(h, params["embed"].T, cast)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def loss(config, params, x, y, cast):
    """Mean next-token cross entropy over a batch of sequences x (B, S)."""
    nll = [_seq_nll(params, x[b], config, cast) for b in range(x.shape[0])]
    return cast(jnp.mean(jnp.stack(nll)))


def score(config, params, x, y, cast):
    """The eval score the benchmark asks of the program: -mean CE."""
    return -loss(config, params, x, y, cast)


@functools.lru_cache(maxsize=8)
def _seq_grad(config_json: str, cast):
    c = json.loads(config_json)

    def g(params, tokens):
        grads = jax.grad(lambda p: _seq_nll(p, tokens, c, cast))(params)
        return jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                                for l in jax.tree_util.tree_leaves(grads)])
    return jax.jit(g)


def client_stats(config, p0, xi, yi, bs, kb, cast):
    """The full-data gradient of one client (flat) and Eq. 7's sigma^2,
    one sequence at a time: a batch's gradient of a mean over equal-length
    sequences is the mean of their gradients."""
    grad = _seq_grad(json.dumps(config, sort_keys=True), cast)
    full, parts = 0.0, [0.0] * kb
    for s in range(xi.shape[0]):
        g = grad(p0, xi[s])
        full = full + g
        if s < kb * bs:
            parts[s // bs] = parts[s // bs] + g
    full = full / xi.shape[0]
    dev = [jnp.sum(jnp.square(pk / bs - full)) for pk in parts]
    return full, jnp.mean(jnp.stack(dev))
