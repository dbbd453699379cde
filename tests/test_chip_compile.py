"""The uplink codec kernels compile for a TPU v5e chip at phase-A width.

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described, not attached: these tests catch what interpret mode cannot
(tiling, VMEM limits) without a chip.  The topology is described inside a
fixture, never at import, so every pytest worker collects the same tests
and only the worker running this file loads the TPU library.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.quantize import (qsgd_dequantize, qsgd_quantize,
                                    rowwise_absmax)
from repro.kernels.topk_threshold import topk_threshold

# phase A of chip_smoke.py: m = 100 clients, padded by kernels.ops to the
# 8-row sublane boundary, and LeNet's flattened update width
ROWS, D = 104, 47_571
BITS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _lower(name, spec):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=spec)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=spec)
    if name == "rowwise_absmax":
        return rowwise_absmax.lower(f32(ROWS, D), interpret=False)
    if name == "qsgd_quantize":
        return qsgd_quantize.lower(f32(ROWS, D), f32(ROWS, D), f32(ROWS, 1),
                                   bits=BITS, interpret=False)
    if name == "qsgd_dequantize":
        return qsgd_dequantize.lower(i32(ROWS, D), f32(ROWS, 1), bits=BITS,
                                     interpret=False)
    return topk_threshold.lower(f32(ROWS, D), k=math.ceil(0.01 * D),
                                interpret=False)


@pytest.mark.parametrize("name", ["rowwise_absmax", "qsgd_quantize",
                                  "qsgd_dequantize", "topk_threshold"])
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    hlo = _lower(name, one_chip).compile().as_text()
    assert "tpu_custom_call" in hlo
