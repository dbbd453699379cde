"""LeNet's convolutions written as contractions (`repro.models.lenet`).

The reference here is the `lax.conv_general_dilated` formulation the model
had before: the same valid 5x5 convolution.  Under a `vmap` over
per-client weights that formulation lowers to a convolution grouped by
the client count; the contraction lowers to a batched dot, which the
structural tests check on the HostVmap local update and the vmapped
eval."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl.placement.host import (HostVmap, cached_update,
                                     make_client_update)
from repro.models import lenet
from repro.optim import sgd

KEY = jax.random.PRNGKey(0)


def _lax_conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "OIHW", "NHWC"))
    return y + b[None, None, None, :]


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _inputs(size, channels, batch):
    cfg = lenet.LeNetConfig(in_size=size, in_channels=channels)
    kp, kx, ky, kb = jax.random.split(KEY, 4)
    params = lenet.init_params(kp, cfg)
    # nonzero biases, so that the bias term is checked too
    params = {k: (v + 0.1 * jax.random.normal(kb, v.shape)
                  if k.endswith("_b") else v) for k, v in params.items()}
    batch = {"x": jax.random.normal(kx, (batch, size, size, channels)),
             "y": jax.random.randint(ky, (batch,), 0, cfg.n_classes)}
    return cfg, params, batch


@pytest.mark.parametrize("batch", [1, 32])
@pytest.mark.parametrize("size,channels", [(28, 1), (32, 3)],
                         ids=["emnist", "cifar"])
def test_conv_matches_lax_conv(size, channels, batch, monkeypatch):
    cfg, params, data = _inputs(size, channels, batch)
    h = jax.random.normal(jax.random.PRNGKey(7),
                          (batch, (size - 4) // 2, (size - 4) // 2, cfg.c1))
    grad = jax.grad(lambda p, b: lenet.loss_fn(p, b)[0])

    def run():
        return (lenet._conv(data["x"], params["conv1_w"], params["conv1_b"]),
                lenet._conv(h, params["conv2_w"], params["conv2_b"]),
                lenet.apply(params, data["x"]), grad(params, data))

    with jax.default_matmul_precision("highest"):
        got = run()
        monkeypatch.setattr(lenet, "_conv", _lax_conv)
        want = run()
    assert got[0].shape == (batch, size - 4, size - 4, cfg.c1)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == jnp.float32
        assert _rel(g, w) <= 1e-5


M, N_SLOTS, N_VAL = 4, 40, 12
_GROUPS = re.compile(r"(feature|batch)_group_count = (\d+)")


def _grouped(text: str):
    """The group counts above 1 of the convolutions in a lowered module."""
    return [(kind, int(n)) for kind, n in _GROUPS.findall(text) if int(n) > 1]


def _shapes():
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    one = jax.eval_shape(lambda: lenet.init_params(KEY, lenet.LeNetConfig()))
    stacked = jax.tree_util.tree_map(lambda l: f32(M, *l.shape), one)
    return stacked, (f32(M, N_SLOTS, 28, 28, 1), i32(M, N_SLOTS), f32(M),
                     jax.ShapeDtypeStruct((M, 2), jnp.uint32)), \
        (f32(M, N_VAL, 28, 28, 1), i32(M, N_VAL))


def _lowered(update):
    """StableHLO text of the vmapped local update and of the vmapped eval."""
    opt, step = update
    stacked, data, val = _shapes()
    opt_state = jax.eval_shape(jax.vmap(opt.init), stacked)
    evaluate = jax.jit(lambda p, x, y: HostVmap().eval_traced(
        lenet.accuracy, p, x, y))
    return (step.lower(stacked, opt_state, *data).as_text(),
            evaluate.lower(stacked, *val).as_text())


def test_vmapped_update_and_eval_have_no_grouped_convolution():
    update, evaluate = _lowered(cached_update(lenet.loss_fn, 2, 8, 0.1, 0.9))
    assert "dot_general" in update and "dot_general" in evaluate
    assert _grouped(update) == [] and _grouped(evaluate) == []


def test_grouped_convolution_detector_sees_vmapped_lax_conv(monkeypatch):
    """The structural test's detector, on the formulation it guards
    against: `vmap` over M clients' `lax.conv` weights groups by M."""
    monkeypatch.setattr(lenet, "_conv", _lax_conv)
    opt = sgd(0.1, momentum=0.9)

    class Fl:
        local_steps, batch_size = 2, 8

    step = jax.jit(jax.vmap(make_client_update(lenet.loss_fn, opt, Fl)))
    update, evaluate = _lowered((opt, step))
    assert ("feature", M) in _grouped(update)
    assert ("feature", M) in _grouped(evaluate)
