"""Plain float32 LeNet-5: the reference beside `lenet-paper.json`.

Written from the paper's description of the model (two 5x5 valid
convolutions with tanh, 2x2 max pooling after each, dense layers of 120
and 84 with tanh, 47 logits) in straightforward `jax.numpy` at the
highest matmul precision.  It imports nothing of the program.  `init` is
the benchmark's own weight maker: the program runs the weights it makes,
and the reference makes the same ones again from the same key.

``cast`` is applied to every operand of a contraction and to every
stored value; the reference passes the identity, the control rounds to a
lower precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
VMAP_CLIENTS = True         # a client's model is small: vmap over clients
# the control computes one precision below the configuration's float32:
# bfloat16 values and bfloat16 operands of every contraction
CONTROL_DTYPE = "bfloat16"
# the reference runs on the host: the v5e compiler does not finish a
# float32 convolution's gradient at the highest precision (it ran the
# chip machine's 40 GiB of host memory out)
DEVICE = "cpu"


def init(config: dict, key):
    """Fan-in normal weights, zero biases; the program's parameter tree."""
    ks = jax.random.split(key, 6)
    c1, c2, f1, f2 = (config[k] for k in ("c1", "c2", "fc1", "fc2"))
    cin, ncls = config["in_channels"], config["n_classes"]
    s = (config["in_size"] - 4) // 2
    s = (s - 4) // 2
    flat = c2 * s * s

    def conv(k, cin_, cout):
        return (jax.random.normal(k, (cout, cin_, 5, 5), jnp.float32)
                / math.sqrt(25 * cin_))

    def fc(k, din, dout):
        return jax.random.normal(k, (din, dout), jnp.float32) / math.sqrt(din)

    z = lambda d: jnp.zeros((d,), jnp.float32)
    return {"conv1_w": conv(ks[0], cin, c1), "conv1_b": z(c1),
            "conv2_w": conv(ks[1], c1, c2), "conv2_b": z(c2),
            "fc1_w": fc(ks[2], flat, f1), "fc1_b": z(f1),
            "fc2_w": fc(ks[3], f1, f2), "fc2_b": z(f2),
            "out_w": fc(ks[4], f2, ncls), "out_b": z(ncls)}


def _conv(x, w, b, cast):
    y = jax.lax.conv_general_dilated(
        cast(x), cast(w), (1, 1), "VALID",
        dimension_numbers=("NHWC", "OIHW", "NHWC"), precision=HI)
    return cast(y + b)


def _pool(x):
    n, h, w, c = x.shape
    return jnp.max(x.reshape(n, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def _dense(x, w, b, cast):
    return cast(jnp.matmul(cast(x), cast(w), precision=HI) + b)


def logits(params, x, cast):
    h = _pool(cast(jnp.tanh(_conv(x, params["conv1_w"], params["conv1_b"],
                                  cast))))
    h = _pool(cast(jnp.tanh(_conv(h, params["conv2_w"], params["conv2_b"],
                                  cast))))
    h = h.reshape(h.shape[0], -1)
    h = cast(jnp.tanh(_dense(h, params["fc1_w"], params["fc1_b"], cast)))
    h = cast(jnp.tanh(_dense(h, params["fc2_w"], params["fc2_b"], cast)))
    return _dense(h, params["out_w"], params["out_b"], cast)


def loss(config, params, x, y, cast):
    """Mean cross entropy of a batch of images x (B, H, W, C), labels y."""
    z = logits(params, x, cast)
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, y[:, None].astype(jnp.int32), axis=-1)
    return cast(jnp.mean(lse - picked[:, 0]))


def score(config, params, x, y, cast):
    """The eval score the benchmark asks of the program: -mean CE."""
    return -loss(config, params, x, y, cast)
