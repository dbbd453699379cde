"""lenet-paper: how the benchmark runs the program's LeNet-5.

The system under test is `repro.models.lenet` (its loss, trained by the
engine's local update).  The weights are the benchmark's own, made on the
device in one jitted call by the reference file's `init`.  The eval score
the fused chunk-end eval computes is -mean cross entropy over a client's
validation images: a continuous score, so that the program's and the
reference's can be compared to rounding.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.models import lenet


def loss_fn(params, batch):
    return lenet.loss_fn(params, batch)


def acc_fn(params, batch):
    return -lenet.loss_fn(params, batch)[0]


def program(config: dict, ref) -> dict:
    """The functions the job hands `run_federated`; stable across calls
    so that its compiled-step caches hit on every job."""
    return {"model_init": jax.jit(partial(ref.init, config)),
            "loss_fn": loss_fn, "acc_fn": acc_fn}


def forward_flops(config: dict) -> float:
    """Multiply-adds x 2 of one image's forward pass, from the shapes."""
    s1 = config["in_size"] - 4                   # conv1 output side
    s2 = s1 // 2 - 4                             # conv2 output side
    flat = config["c2"] * (s2 // 2) ** 2
    macs = (s1 * s1 * config["c1"] * 25 * config["in_channels"]
            + s2 * s2 * config["c2"] * 25 * config["c1"]
            + flat * config["fc1"] + config["fc1"] * config["fc2"]
            + config["fc2"] * config["n_classes"])
    return 2.0 * macs


def train_flops_per_round(config: dict, mix: dict) -> float:
    """Forward and backward (3 x forward) of every sample a round trains
    on: m clients x local steps x batch."""
    samples = mix["data"]["m"] * mix["local_steps"] * mix["batch_size"]
    return 3.0 * forward_flops(config) * samples


