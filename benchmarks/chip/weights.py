"""Weights and token batches made from the run's seed.

Both are the benchmark's own: the program under test and the plain
reference are handed the same weights, and neither makes any. The
weights are made on the device, in the type they are trained in, by one
jitted call; each leaf's values depend only on the seed and the leaf's
path, so a leaf's name fixes its initial distribution:

  scale, ln_x_scale        ones (norm gains)
  bias, ln_x_bias          zeros
  mix_mu, mix_x, cmu_*     uniform [0, 1)  (token-shift lerp weights)
  w0                       -1 + 0.5 N(0, 1) (per-channel base decay)
  bonus_u                  0.5 N(0, 1)
  anything else            0.02 N(0, 1)    (projections, embedding, head)

Token batches follow the program's TokenPipeline scheme: numpy's Philox
keyed by the seed, with the step in the counter's high word, ids drawn
uniformly from the configuration's vocabulary.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

_UNIFORM = ("mix_mu", "mix_x", "cmu_k", "cmu_r")


def leaf_name(path) -> str:
    """'/'-joined pytree path of a leaf, as the checkpointer names it."""
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/" + "/".join(parts)


def _leaf_init(name: str, key, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    if last in ("scale", "ln_x_scale"):
        return jnp.ones(shape, dtype)
    if last in ("bias", "ln_x_bias"):
        return jnp.zeros(shape, dtype)
    if last in _UNIFORM:
        return jax.random.uniform(key, shape, jnp.float32).astype(dtype)
    if last == "w0":
        return (-1.0 + 0.5 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    std = 0.5 if last == "bonus_u" else 0.02
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def seed_key(seed: int):
    """A PRNG key that depends on every bit of ``seed``: jax.random.key
    keeps only its low 32 bits, so the high bits are folded in."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(shapes: Any, seed: int, dtype=None) -> Any:
    """Weights shaped like ``shapes`` (a pytree of ShapeDtypeStructs),
    made on the default device in one jitted call. ``dtype`` overrides
    each leaf's type (the reference's lower-precision control)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [leaf_name(p) for p, _ in flat]

    def build(key):
        leaves = [_leaf_init(n, jax.random.fold_in(key, i), s.shape,
                             dtype or s.dtype)
                  for i, (n, (_, s)) in enumerate(zip(names, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


def batch_at(seed: int, step: int, batch: int, seq: int,
             vocab: int) -> Dict[str, np.ndarray]:
    """Tokens and next-token labels of one step, on the host."""
    rng = np.random.Generator(np.random.Philox(key=seed,
                                               counter=[0, 0, 0, step]))
    tokens = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
