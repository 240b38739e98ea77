"""The comparisons that decide a run's ``correct``.

Training (every cell): the program's first three steps against the plain
reference's, from the same weights and batches:

  loss_gap    worst |loss_p - loss_r| / |loss_r| over the steps;
  grad_gap    worst leaf |‖g_p‖ - ‖g_r‖| / max(‖g_r‖, median leaf's),
              g being the first step's clipped gradient as the optimizer
              got it (the program's: its first moment / (1 - b1));
  update_gap  the same measure on ‖p_3 - p_0‖, the change of each
              parameter leaf over the three steps, over the leaves
              whose reference gradient is at least a thousandth of the
              median leaf's (a leaf below that moves by round-off alone).

Storage (cells that save or restore): every leaf compared bit for bit by
a fingerprint of its 32-bit words, taken on the device from the state
that was handed to ``save`` and on the host from what each replica hands
back. Two sums modulo 2**32: of the words, and of each word times an odd
weight of its position, so any altered, lost or moved word changes one.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.weights import leaf_name

MOVES_FLOOR = 1e-3  # of the median leaf's reference gradient


def loss_gap(prog: Iterable[float], ref: Iterable[float]) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Optional[Iterable[str]] = None) -> tuple:
    """(worst gap, its leaf) of per-leaf norms, each measured against
    the larger of the reference leaf's norm and the median leaf's."""
    names = sorted(ref if keep is None else keep)
    med = float(np.median([ref[n] for n in ref]))
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], med) for n in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def moving_leaves(ref_grads: Dict[str, float]) -> list:
    med = float(np.median(list(ref_grads.values())))
    return [n for n, g in ref_grads.items() if g >= MOVES_FLOOR * med]


def training_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """prog, ref: {"losses", "grad_norms", "update_norms"}."""
    keep = moving_leaves(ref["grad_norms"])
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_gap": leaf_gap(prog["grad_norms"], ref["grad_norms"])[0],
            "update_gap": leaf_gap(prog["update_norms"],
                                   ref["update_norms"], keep)[0]}


# -- fingerprints ------------------------------------------------------------
def _words_fp(w):
    idx = jax.lax.iota(jnp.uint32, w.shape[0])
    return jnp.stack([jnp.sum(w, dtype=jnp.uint32),
                      jnp.sum(w * (idx * 2 + 1), dtype=jnp.uint32)])


def _leaf_words(x):
    if x.dtype.itemsize != 4:
        raise TypeError(f"fingerprints take 32-bit leaves, not {x.dtype}")
    return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)


@jax.jit
def _device_fps(leaves):
    return jnp.stack([_words_fp(_leaf_words(x)) for x in leaves])


def device_fingerprints(tree) -> Dict[str, tuple]:
    """{leaf name: (sum, weighted sum)} of a pytree of device arrays."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    fps = np.asarray(_device_fps([x for _, x in flat]))
    return {leaf_name(p): (int(a), int(b))
            for (p, _), (a, b) in zip(flat, fps)}


def host_fingerprint(arr: np.ndarray) -> tuple:
    w = np.ascontiguousarray(arr).reshape(-1).view(np.uint32).astype(
        np.uint64)
    weights = np.arange(w.size, dtype=np.uint64) * 2 + 1
    mask = np.uint64(0xFFFFFFFF)
    return (int(np.sum(w, dtype=np.uint64) & mask),
            int(np.sum(w * weights, dtype=np.uint64) & mask))
