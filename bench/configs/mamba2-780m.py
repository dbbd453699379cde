"""mamba2-780m: how the benchmark runs the program's Mamba-2.

The system under test is the registry's mamba2-780m (`repro.configs`) run
through the scanned model (`repro.models.scan.loss_fn`, with each layer
recomputed in the backward pass), at the widths of
`bench/configs/mamba2-780m.json` and its depth.  The weights are the
benchmark's own (`mamba2-780m_ref.init`), made on the device in one
jitted call in bfloat16.  The eval score is -mean next-token cross
entropy over a client's validation sequences, as `repro.launch.train`
reports it.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import jax

WIDTHS = {"d_model": "d_model"}
SSM_WIDTHS = {"d_state": "d_state", "d_conv": "d_conv", "expand": "expand",
              "headdim": "head_dim", "ngroups": "n_groups",
              "chunk_size": "chunk_size"}


def rows(c: dict) -> int:
    """Embedding rows: ``vocab_size`` padded up to a multiple of
    ``pad_vocab_size_multiple``, as the published checkpoint holds it."""
    pad = int(c.get("pad_vocab_size_multiple", 1))
    return -(-int(c["vocab_size"]) // pad) * pad


@functools.lru_cache(maxsize=4)
def _program(config_json: str):
    from repro.configs import get_config
    from repro.models import scan
    c = json.loads(config_json)
    # the depth of the cut, and the published checkpoint's embedding rows
    # (the registry holds 50,280)
    cfg = dataclasses.replace(get_config("mamba2-780m"),
                              n_layers=int(c["n_layer"]), vocab_size=rows(c))
    for ours, theirs in WIDTHS.items():
        if getattr(cfg, theirs) != c[ours]:
            raise ValueError(f"the program's {theirs} {getattr(cfg, theirs)}"
                             f" is not the configuration's {c[ours]}")
    for ours, theirs in SSM_WIDTHS.items():
        if getattr(cfg.ssm, theirs) != c[ours]:
            raise ValueError(f"the program's ssm.{theirs} is not the "
                             f"configuration's {ours} {c[ours]}")
    if (cfg.param_dtype, cfg.compute_dtype) != (c["param_dtype"],
                                                c["compute_dtype"]):
        raise ValueError("the program's dtypes are not the configuration's")
    remat = bool(c["remat"])

    def loss_fn(p, b):
        return scan.loss_fn(p, cfg, {"tokens": b["x"]}, remat=remat)

    def acc_fn(p, b):
        return -scan.loss_fn(p, cfg, {"tokens": b["x"]}, remat=remat)[0]

    return loss_fn, acc_fn


def program(config: dict, ref) -> dict:
    """The functions the job hands `run_federated`; stable across calls
    so that its compiled-step caches hit on every job."""
    loss_fn, acc_fn = _program(json.dumps(config, sort_keys=True))
    return {"model_init": jax.jit(functools.partial(ref.init, config)),
            "loss_fn": loss_fn, "acc_fn": acc_fn}


def layer_forward_flops(c: dict) -> float:
    """FLOPs of one layer's forward pass per token, from the shapes: the
    input and output projections, the depthwise convolution, and the
    chunked SSD -- within a chunk C.B^T and the masked (c x c) block
    times x for every head, across chunks the chunk state (x B^T) and
    its read-out (C . state) for every head."""
    d_inner = c["expand"] * c["d_model"]
    heads = d_inner // c["headdim"]
    gn = c["ngroups"] * c["d_state"]
    conv_dim = d_inner + 2 * gn
    d_proj = 2 * d_inner + 2 * gn + heads
    q, p, n = c["chunk_size"], c["headdim"], c["d_state"]
    macs = (c["d_model"] * d_proj + conv_dim * c["d_conv"]
            + q * gn + heads * q * p           # intra-chunk
            + 2 * heads * p * n                # inter-chunk
            + d_inner * c["d_model"])
    return 2.0 * macs


def head_forward_flops(c: dict) -> float:
    return 2.0 * c["d_model"] * rows(c)


def train_flops_per_round(config: dict, mix: dict) -> float:
    """3 x forward of every sequence a round trains on: m clients x local
    steps x batch; the head over the S - 1 positions the loss reads.
    Recomputed work is not counted."""
    seq = mix["data"]["seq"]
    per_seq = (seq * config["n_layer"] * layer_forward_flops(config)
               + (seq - 1) * head_forward_flops(config))
    return (3.0 * per_seq * mix["data"]["m"] * mix["local_steps"]
            * mix["batch_size"])
