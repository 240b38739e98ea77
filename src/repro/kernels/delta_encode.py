"""Changed-block scan Pallas kernel (TPU target) — checkpoint delta
encoding on-device.

The Assise-layer redundant-write elimination needs a changed-block bitmap
over each encoded checkpoint leaf (ckpt/checkpoint.py writes only the
changed byte ranges). The scan is pure memory bandwidth: read 2x leaf
bytes, write one flag per block.

The kernel takes the leaf as 32-bit words viewed on the host
(``np.frombuffer(raw, np.uint32)``), never as bytes: v5e cannot compare
``uint8`` vectors, and a byte array laid out as ``(n, 4)`` on the device
pads its last dimension to 128 lanes (a 32x blow-up). Each block of
``block`` bytes is a ``(block // 512, 128)`` slab of words, so every
VMEM tile keeps the TPU's (8, 128) layout, and ``bpt`` blocks stream
through VMEM per grid step. The flags leave lane-dense: each block's flag
is broadcast across one 128-lane row of the ``(tiles, bpt, 128)`` output.

Grid: (n_blocks / bpt,).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
WORD = 4  # bytes per uint32 word


def _delta_kernel(new_ref, old_ref, mask_ref):
    diff = (new_ref[...] != old_ref[...]).astype(jnp.int32)  # (bpt, r, 128)
    rows = jnp.max(diff, axis=1)                              # (bpt, 128)
    flag = jnp.max(rows, axis=1, keepdims=True)               # (bpt, 1)
    mask_ref[0] = jnp.broadcast_to(flag, mask_ref.shape[1:])


@partial(jax.jit, static_argnames=("block", "bpt", "interpret"))
def delta_mask(new, old, *, block: int = 1 << 16, bpt: int = 8,
               interpret: bool):
    """new, old: 1-D uint32 word arrays of equal length, a multiple of
    ``block * bpt / 4`` words; ``block`` (bytes) a multiple of 512.

    Returns an int32 mask of length n_blocks (1 = block changed)."""
    assert new.shape == old.shape and new.ndim == 1, (new.shape, old.shape)
    assert new.dtype == old.dtype == jnp.uint32, (new.dtype, old.dtype)
    assert block % (WORD * LANES) == 0, block
    rows = block // (WORD * LANES)
    n_blocks = new.shape[0] * WORD // block
    assert n_blocks % bpt == 0, (new.shape, block, bpt)
    tiles = n_blocks // bpt
    nf = new.reshape(n_blocks, rows, LANES)
    of = old.reshape(n_blocks, rows, LANES)
    mask = pl.pallas_call(
        _delta_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((bpt, rows, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((bpt, rows, LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bpt, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((tiles, bpt, LANES), jnp.int32),
        interpret=interpret,
        name="delta_mask",
    )(nf, of)
    return mask[:, :, 0].reshape(n_blocks)
