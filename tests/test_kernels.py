"""Pallas delta_mask kernel vs the pure-jnp oracle (interpret mode)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.delta_encode import delta_mask

RNG = np.random.default_rng(42)


def _words(b: np.ndarray):
    """Bytes as the kernel takes them: uint32 words viewed on the host."""
    return jnp.asarray(b.view(np.uint32))


@pytest.mark.parametrize("block,bpt", [(512, 4), (2048, 8)])
def test_delta_mask(block, bpt):
    n = block * bpt * 4
    new = RNG.integers(0, 255, n).astype(np.uint8)
    old = new.copy()
    old[block + 3] ^= 0xFF  # flip one byte in block 1
    old[3 * block: 3 * block + 10] ^= 1  # and a run in block 3
    m = delta_mask(_words(new), _words(old), block=block, bpt=bpt,
                   interpret=True)
    exp, _ = ref.delta_encode_ref(jnp.asarray(new), jnp.asarray(old), block)
    np.testing.assert_array_equal(np.asarray(m, bool), np.asarray(exp))
    assert np.flatnonzero(np.asarray(m)).tolist() == [1, 3]


def test_delta_mask_identical_is_empty():
    n = 2048 * 8
    new = RNG.integers(0, 255, n).astype(np.uint8)
    m = delta_mask(_words(new), _words(new), block=2048, interpret=True)
    assert int(np.asarray(m).sum()) == 0
