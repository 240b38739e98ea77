"""Compile-only checks for a described v5e chip (no chip attached): the
checkpoint path's ``delta_mask`` kernel at the default 64 KB block, for a
small leaf, the chip smoke's largest leaf (its 16,384 x 2048 f32
embedding share, 128 MiB) and rwkv6-1.6b's whole 65,536 x 2048 f32
embedding (512 MiB), each as uint32 words. The TPU compiler
refuses what interpret mode accepts (the (8, 128) block rule, comparisons
the chip cannot do), and a layout that pads the words would need many
times the leaf in temporary memory."""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.ckpt.checkpoint import CheckpointConfig
from repro.kernels.delta_encode import delta_mask

BLOCK = CheckpointConfig().delta_block  # 64 KB
LEAF_BYTES = {"small": 8 * BLOCK, "smoke_embedding": 16_384 * 2048 * 4,
              "embedding": 65_536 * 2048 * 4}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("leaf", sorted(LEAF_BYTES))
def test_delta_mask_compiles_for_v5e(leaf, one_chip, no_compile_cache):
    words = jax.ShapeDtypeStruct((LEAF_BYTES[leaf] // 4,), jnp.uint32,
                                 sharding=one_chip)
    compiled = delta_mask.lower(words, words, block=BLOCK,
                                interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
