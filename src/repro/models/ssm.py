"""Mamba2 / SSD (state-space duality) blocks [arXiv:2405.21060].

Training/prefill uses the chunked SSD algorithm: quadratic attention-like
intra-chunk blocks + a linear inter-chunk state recurrence (lax.scan over
chunks).  Decode is the O(1) state update — the reason SSM archs serve
long_500k with no KV cache at all (DESIGN.md §6).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_apply, dense_init


class SSMCache(NamedTuple):
    conv: jnp.ndarray     # (B, d_conv-1, conv_dim) trailing conv inputs
    state: jnp.ndarray    # (B, nh, head_dim, d_state)


def ssm_dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, n_heads, conv_dim


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> SSMCache:
    s = cfg.ssm
    d_inner, nh, conv_dim = ssm_dims(cfg)
    return SSMCache(
        conv=jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
        state=jnp.zeros((batch, nh, s.head_dim, s.d_state), dtype))


def ssm_init(key, cfg: ModelConfig):
    s = cfg.ssm
    d, dt = cfg.d_model, cfg.pdtype
    d_inner, nh, conv_dim = ssm_dims(cfg)
    ks = jax.random.split(key, 4)
    # in_proj emits [z, xBC, dt]
    d_proj = 2 * d_inner + 2 * s.n_groups * s.d_state + nh
    a_init = jnp.log(jnp.linspace(1.0, 16.0, nh))
    dt_init = jnp.log(jnp.exp(
        jnp.exp(jax.random.uniform(ks[2], (nh,)) *
                (math.log(s.dt_max) - math.log(s.dt_min)) + math.log(s.dt_min))
        ) - 1.0 + 1e-9)  # inverse softplus of sampled dt
    return {
        "in_proj": dense_init(ks[0], d, d_proj, dt),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, conv_dim), jnp.float32)
                   / math.sqrt(s.d_conv)).astype(dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": a_init.astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": dt_init.astype(jnp.float32),
        "norm_scale": jnp.zeros((d_inner,), dt),
        "out_proj": dense_init(ks[3], d_inner, d, dt),
    }


def _causal_conv(x, w, b, carry: Optional[jnp.ndarray]):
    """x: (B,S,C); w: (K,C) depthwise; carry: (B,K-1,C) previous inputs."""
    K = w.shape[0]
    if carry is None:
        carry = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([carry, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(K))
    new_carry = xp[:, -(K - 1):, :] if K > 1 else carry
    return jax.nn.silu(out + b[None, None, :]), new_carry


def _segsum(dA):
    """dA: (..., c, h) -> L: (..., h, c, c), L[i,j]=exp(sum_{j<k<=i} dA_k), i>=j."""
    cs = jnp.cumsum(dA, axis=-2)                               # (..., c, h)
    cs = jnp.moveaxis(cs, -1, -2)                              # (..., h, c)
    diff = cs[..., :, None] - cs[..., None, :]                 # (..., h, c, c)
    c = dA.shape[-2]
    mask = jnp.tril(jnp.ones((c, c), bool))
    # mask before exp: above the diagonal diff is a positive sum that
    # overflows to inf over a long chunk, and where()'s gradient would
    # then multiply that inf by zero
    return jnp.exp(jnp.where(mask, diff, -jnp.inf))


def ssd_scan(x, dt, A, B, C, chunk: int,
             init_state: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD.  x: (b,s,h,p); dt: (b,s,h); A: (h,); B,C: (b,s,g,n).

    Returns (y (b,s,h,p), final_state (b,h,p,n)).  All math in float32.
    """
    b, s, h, p = x.shape
    g = B.shape[2]
    hg = h // g
    c = min(chunk, s)
    pad = (-s) % c
    if pad:
        zf = lambda a: jnp.concatenate(
            [a, jnp.zeros((b, pad) + a.shape[2:], a.dtype)], axis=1)
        x, dt, B, C = zf(x), zf(dt), zf(B), zf(C)
    nc = x.shape[1] // c
    xr = x.reshape(b, nc, c, h, p).astype(jnp.float32)
    dtr = dt.reshape(b, nc, c, h).astype(jnp.float32)
    Br = B.reshape(b, nc, c, g, B.shape[-1]).astype(jnp.float32)
    Cr = C.reshape(b, nc, c, g, C.shape[-1]).astype(jnp.float32)

    dA = dtr * A[None, None, None, :]                          # (b,nc,c,h)
    xdt = xr * dtr[..., None]                                  # (b,nc,c,h,p)
    L = _segsum(dA)                                            # (b,nc,h,c,c)
    # intra-chunk: Y[i] = sum_{j<=i} (C_i . B_j) L_ij xdt_j
    xg = xdt.reshape(b, nc, c, g, hg, p)
    Lg = L.reshape(b, nc, g, hg, c, c)                         # b l g k i j
    cb = jnp.einsum("blign,bljgn->bligj", Cr, Br)              # (b,nc,c,g,c)
    y_diag = jnp.einsum("bligj,blgkij,bljgkp->bligkp", cb, Lg, xg)
    # ^ dims: l chunk, i/j intra positions, g group, k head-in-group, p head dim
    y_diag = y_diag.reshape(b, nc, c, h, p)

    # chunk states: S_l = sum_j exp(cs_last - cs_j) xdt_j B_j^T  (b,nc,h,p,n)
    cs = jnp.cumsum(dA, axis=2)
    decay = jnp.exp(cs[:, :, -1:, :] - cs)                     # (b,nc,c,h)
    decay_g = decay.reshape(b, nc, c, g, hg)
    states = jnp.einsum("blcgk,blcgkp,blcgn->blgkpn", decay_g, xg, Br)
    states = states.reshape(b, nc, h, p, states.shape[-1])

    # inter-chunk recurrence
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))                 # (b,nc,h)
    s0 = (jnp.zeros_like(states[:, 0]) if init_state is None
          else init_state.astype(jnp.float32))

    def step(carry, inp):
        st, dec = inp
        new = carry * dec[..., None, None] + st
        return new, carry                                      # emit state BEFORE chunk

    final, prev_states = jax.lax.scan(
        step, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)              # (b,nc,h,p,n)

    # inter-chunk output: Y_off[i] = exp(cs_i) C_i . S_prev
    pg = prev_states.reshape(b, nc, g, hg, p, prev_states.shape[-1])
    y_off = jnp.einsum("blign,blgkpn->bligkp", Cr, pg)
    y_off = y_off.reshape(b, nc, c, h, p) * jnp.exp(cs)[..., None]
    y = (y_diag + y_off).reshape(b, nc * c, h, p)
    if pad:
        y = y[:, :s]
    return y, final


def ssd_decode_step(x, dt, A, B, C, state):
    """Single-token state update.  x: (b,h,p); dt: (b,h); B,C: (b,g,n);
    state: (b,h,p,n) -> (y (b,h,p), new_state)."""
    b, h, p = x.shape
    g = B.shape[1]
    hg = h // g
    dA = jnp.exp(dt.astype(jnp.float32) * A[None, :])          # (b,h)
    xdt = (x * dt[..., None]).astype(jnp.float32)
    Bh = jnp.repeat(B.astype(jnp.float32), hg, axis=1)         # (b,h,n)
    Ch = jnp.repeat(C.astype(jnp.float32), hg, axis=1)
    new_state = state.astype(jnp.float32) * dA[..., None, None] \
        + xdt[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y, new_state


def _gated_rmsnorm(y, z, scale):
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    return y * jax.lax.rsqrt(var + 1e-6) * (1.0 + scale.astype(jnp.float32))


def ssm_apply(params, cfg: ModelConfig, x,
              cache: Optional[SSMCache] = None, *, decode: bool = False):
    """Mamba2 block.  x: (B,S,d) -> (y, new_cache)."""
    s, cd = cfg.ssm, cfg.cdtype
    d_inner, nh, conv_dim = ssm_dims(cfg)
    B_, S_, _ = x.shape
    proj = dense_apply(params["in_proj"], x, cd)
    z = proj[..., :d_inner]
    xBC = proj[..., d_inner:d_inner + conv_dim]
    dt_raw = proj[..., d_inner + conv_dim:]
    xBC, new_conv = _causal_conv(xBC, params["conv_w"].astype(cd),
                                 params["conv_b"].astype(cd),
                                 cache.conv if cache is not None else None)
    xs = xBC[..., :d_inner]
    Bc = xBC[..., d_inner:d_inner + s.n_groups * s.d_state]
    Cc = xBC[..., d_inner + s.n_groups * s.d_state:]
    Bc = Bc.reshape(B_, S_, s.n_groups, s.d_state)
    Cc = Cc.reshape(B_, S_, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                         params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"])
    xh = xs.reshape(B_, S_, nh, s.head_dim)

    if decode:
        assert S_ == 1 and cache is not None
        y, new_state = ssd_decode_step(
            xh[:, 0].astype(jnp.float32), dt[:, 0], A, Bc[:, 0], Cc[:, 0],
            cache.state)
        y = y[:, None]
    else:
        init = cache.state if cache is not None else None
        y, new_state = ssd_scan(xh, dt, A, Bc, Cc, s.chunk_size, init)
    y = y + params["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B_, S_, d_inner)
    y = _gated_rmsnorm(y, z, params["norm_scale"]).astype(cd)
    out = dense_apply(params["out_proj"], y, cd)
    new_cache = SSMCache(conv=new_conv, state=new_state.astype(
        cache.state.dtype if cache is not None else jnp.float32))
    return out, new_cache
