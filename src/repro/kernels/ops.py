"""jit'd public wrappers around the Pallas kernels.

The TPU lowering is the target.  Each wrapper decides its mode when it is
called, from the default backend: compiled on a TPU, interpreted on the
CPU (the same kernel body, executed by the Pallas interpreter), and an
error on any other platform.  Importing this module initialises no
backend.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.mixing_aggregate import mixing_aggregate as _mix
from repro.kernels.pairwise_sqdist import gram_matrix as _gram
from repro.kernels.pairwise_sqdist import pairwise_sqdist as _sqdist
from repro.kernels.quantize import (qsgd_dequantize as _qsgd_deq,
                                    qsgd_quantize as _qsgd_q,
                                    rowwise_absmax as _absmax)
from repro.kernels.topk_threshold import topk_threshold as _topk


def _interpret() -> bool:
    """Pallas mode for the backend the wrapper is called on."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels compile for a TPU and are "
                       f"interpreted on the CPU; backend {platform!r} has "
                       f"neither path")


def _pad_rows(a: jnp.ndarray, mult: int = 8):
    pad = (-a.shape[0]) % mult
    return (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)), pad)


def mixing_aggregate(w: jnp.ndarray, theta: jnp.ndarray, *,
                     dblk: int = 2048) -> jnp.ndarray:
    """Y = W Θ; k/m padded to the TPU sublane boundary, result cropped."""
    k, m = w.shape
    pk, pm = (-k) % 8, (-m) % 8
    w2 = jnp.pad(w, ((0, pk), (0, pm)))
    theta2 = jnp.pad(theta, ((0, pm), (0, 0)))
    out = _mix(w2, theta2, dblk=dblk, interpret=_interpret())
    return out[:k]


def pairwise_sqdist(g: jnp.ndarray, *, dblk: int = 2048) -> jnp.ndarray:
    m = g.shape[0]
    g2, _ = _pad_rows(g)
    return _sqdist(g2, dblk=dblk, interpret=_interpret())[:m, :m]


def gram_matrix(g: jnp.ndarray, *, dblk: int = 2048) -> jnp.ndarray:
    m = g.shape[0]
    g2, _ = _pad_rows(g)
    return _gram(g2, dblk=dblk, interpret=_interpret())[:m, :m]


def qsgd_quantize(x: jnp.ndarray, noise: jnp.ndarray, *, bits: int,
                  dblk: int = 2048):
    """(levels int32, absmax (m,1)) of the QSGD channel codec; rows padded
    to the sublane boundary and cropped."""
    m = x.shape[0]
    x2, _ = _pad_rows(x)
    noise2, _ = _pad_rows(noise)
    interpret = _interpret()
    amax = _absmax(x2, dblk=dblk, interpret=interpret)
    q = _qsgd_q(x2, noise2, amax, bits=bits, dblk=dblk, interpret=interpret)
    return q[:m], amax[:m]


def qsgd_dequantize(q: jnp.ndarray, absmax: jnp.ndarray, *, bits: int,
                    dblk: int = 2048) -> jnp.ndarray:
    m = q.shape[0]
    q2, _ = _pad_rows(q)
    amax2, _ = _pad_rows(absmax)
    return _qsgd_deq(q2, amax2, bits=bits, dblk=dblk,
                     interpret=_interpret())[:m]


def qsgd_roundtrip(x: jnp.ndarray, noise: jnp.ndarray, *, bits: int,
                   dblk: int = 2048) -> jnp.ndarray:
    """Fused channel view: dequantize(quantize(x)) — what the server sees."""
    q, amax = qsgd_quantize(x, noise, bits=bits, dblk=dblk)
    return qsgd_dequantize(q, amax, bits=bits, dblk=dblk)


def topk_threshold(absx: jnp.ndarray, *, k: int, rblk: int = 8
                   ) -> jnp.ndarray:
    """Per-row top-k magnitude cutoff (m, 1); rows padded to rblk."""
    m = absx.shape[0]
    absx2, _ = _pad_rows(absx, mult=rblk)
    return _topk(absx2, k=k, rblk=rblk, interpret=_interpret())[:m]


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    qblk: int = 128, kblk: int = 128) -> jnp.ndarray:
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  qblk=qblk, kblk=kblk, interpret=_interpret())


__all__ = ["mixing_aggregate", "pairwise_sqdist", "gram_matrix",
           "flash_attention", "qsgd_quantize", "qsgd_dequantize",
           "qsgd_roundtrip", "topk_threshold", "ref"]
