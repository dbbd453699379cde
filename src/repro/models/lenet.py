"""LeNet-5 CNN — the paper's model for EMNIST/CIFAR experiments [LeCun 1998].

Functional raw-JAX implementation.  Supports 28x28x1 (EMNIST) and 32x32x3
(CIFAR) inputs via config.

The two convolutions are contractions, not `lax.conv`.  The engine runs the
local update and the eval as a `vmap` over m clients, each with its own
weights, and JAX's batching rule turns a `lax.conv` with batched weights
into one convolution with `feature_group_count = m` (conv1: one input
channel per group), the worst case for the TPU's matrix unit.  `_conv`
instead takes shifted windows of the input, which moves data and rounds
nothing, and contracts them with the weight, one `einsum` per kernel row,
at the default matmul precision, as the convolution ran.  Under the client
`vmap` the forward pass and both gradients are then dots with the client
as a batch dimension (DESIGN.md §3).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class LeNetConfig:
    in_size: int = 28
    in_channels: int = 1
    n_classes: int = 47
    c1: int = 6
    c2: int = 16
    fc1: int = 120
    fc2: int = 84


def _conv_init(key, k, cin, cout):
    fan_in = k * k * cin
    return (jax.random.normal(key, (cout, cin, k, k), jnp.float32)
            / math.sqrt(fan_in))


def _fc_init(key, din, dout):
    return (jax.random.normal(key, (din, dout), jnp.float32) / math.sqrt(din))


def init_params(key, cfg: LeNetConfig) -> Dict[str, jnp.ndarray]:
    ks = jax.random.split(key, 6)
    # spatial size after two (conv5 valid + pool2) stages
    s = cfg.in_size
    s = (s - 4) // 2
    s = (s - 4) // 2
    flat = cfg.c2 * s * s
    return {
        "conv1_w": _conv_init(ks[0], 5, cfg.in_channels, cfg.c1),
        "conv1_b": jnp.zeros((cfg.c1,)),
        "conv2_w": _conv_init(ks[1], 5, cfg.c1, cfg.c2),
        "conv2_b": jnp.zeros((cfg.c2,)),
        "fc1_w": _fc_init(ks[2], flat, cfg.fc1),
        "fc1_b": jnp.zeros((cfg.fc1,)),
        "fc2_w": _fc_init(ks[3], cfg.fc1, cfg.fc2),
        "fc2_b": jnp.zeros((cfg.fc2,)),
        "out_w": _fc_init(ks[4], cfg.fc2, cfg.n_classes),
        "out_b": jnp.zeros((cfg.n_classes,)),
    }


def _conv(x, w, b):
    """Valid stride-1 k x k convolution, (B, H, W, C) x (O, C, k, k) ->
    (B, H-k+1, W-k+1, O), as contractions (module docstring).  The image
    is flattened to (B, C, H*W), so a tap is a shift of the flat axis.
    The k column shifts are stacked once; kernel row i contracts their
    (C, k) axes, shifted by i rows, with ``w[:, :, i, :]``, and the k
    row terms are summed.  Each output row is computed at all W columns
    and the k-1 that wrap round into the next row are dropped."""
    o, c, k, _ = w.shape
    bsz, h, wd, _ = x.shape
    ho, wo, n = h - k + 1, wd - k + 1, (h - k + 1) * wd
    flat = jnp.pad(x.transpose(0, 3, 1, 2).reshape(bsz, c, h * wd),
                   ((0, 0), (0, 0), (0, k - 1)))
    cols = jnp.stack([flat[:, :, j:j + n + (k - 1) * wd] for j in range(k)],
                     axis=2)
    y = sum(jnp.einsum("bcjn,ocj->bon", cols[..., i * wd:i * wd + n],
                       w[:, :, i, :]) for i in range(k))
    return y.reshape(bsz, o, ho, wd)[:, :, :, :wo].transpose(0, 2, 3, 1) + b


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def apply(params, x):
    """x: (B, H, W, C) float32 -> logits (B, n_classes)."""
    h = jnp.tanh(_conv(x, params["conv1_w"], params["conv1_b"]))
    h = _pool(h)
    h = jnp.tanh(_conv(h, params["conv2_w"], params["conv2_b"]))
    h = _pool(h)
    h = h.reshape(h.shape[0], -1)
    h = jnp.tanh(h @ params["fc1_w"] + params["fc1_b"])
    h = jnp.tanh(h @ params["fc2_w"] + params["fc2_b"])
    return h @ params["out_w"] + params["out_b"]


def loss_fn(params, batch):
    """batch: {"x": (B,H,W,C), "y": (B,)} -> (mean CE, metrics)."""
    logits = apply(params, batch["x"])
    lps = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lps, batch["y"][:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    loss = jnp.mean(nll)
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32))
    return loss, {"loss": loss, "acc": acc}


def accuracy(params, batch):
    logits = apply(params, batch["x"])
    return jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32))
