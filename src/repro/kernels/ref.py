"""Pure-jnp oracle for the Pallas kernel (the correctness ground truth):
the mathematical definition, written for clarity not speed."""
from __future__ import annotations

import jax.numpy as jnp


def delta_encode_ref(new, old, block: int):
    """Changed-block scan oracle.

    new, old: 1-D arrays, length divisible by `block`.
    Returns (mask: (n_blocks,) bool  — block differs,
             packed: same shape as new — changed blocks compacted to the
             front (stable order), zero-padded)."""
    n = new.shape[0] // block
    nb = new.reshape(n, block)
    ob = old.reshape(n, block)
    mask = jnp.any(nb != ob, axis=1)
    order = jnp.argsort(~mask, stable=True)  # changed blocks first
    packed = jnp.where(mask[order][:, None], nb[order], 0)
    return mask, packed.reshape(-1)
