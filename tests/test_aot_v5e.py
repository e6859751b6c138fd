"""The device kernels of the main paths compiled for a TPU v5e that is
described, not attached: what the chip's compiler would refuse (a tile
out of line with the layout, too much fast memory) fails here, at no
chip time. Nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU's library."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from curvine_tpu.tpu import pallas_ops

# DeepSeek-V3's fp8 linear weights, (out, in), at blocks of 128 × 128:
# 576 rows are 4.5 blocks
DSV3_FP8 = [(1536, 7168), (24576, 1536), (576, 7168), (32768, 512),
            (7168, 16384), (18432, 7168), (7168, 18432), (2048, 7168),
            (7168, 2048)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape", DSV3_FP8)
def test_fp8_block_dequant_compiles_for_v5e(one_chip, shape):
    o, i = shape
    wq = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((-(-o // 128) * -(-i // 128),),
                                 jnp.float32, sharding=one_chip)
    compiled = pallas_ops._dequant.lower(
        wq, scale, block=(128, 128), dtype=jnp.dtype(jnp.bfloat16),
        interpret=False).compile()
    text = compiled.as_text()
    # one kernel and nothing else on the device, under its own name
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%fp8_block_dequant" in text and " fusion(" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes == 2 * o * i
