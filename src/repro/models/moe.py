"""Mixture-of-Experts: a dropless layer over the experts it holds.

The router spans every expert, each token's top-k is taken over all of
them, and the layer computes the part of the result that its held
experts give (``MoESpec.held_first`` / ``held``; all by default), for
every assignment routed to them. The (token, expert) assignments are
sorted by held expert and run as grouped products
(``jax.lax.ragged_dot``) over those rows only, in passes of a fixed
size; none is dropped. The balance loss is taken over all experts, so it
is exact in a share.

Under a mesh the launch layer's ``expert_map`` runs the same routed part
on every device, over its own tokens (the batch axes) and its own slice
of the experts (the ``model`` axis: expert parallelism), and sums the
slices' parts; the shared experts run outside it, once.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoESpec
from repro.models.layers import apply_mlp, init_mlp, normal_init

Array = jax.Array


def init_moe(key, d_model: int, spec: MoESpec, act: str, dtype) -> dict:
    ks = jax.random.split(key, 5)
    e, f = spec.n_held, spec.d_expert
    p = {
        "router": normal_init(ks[0], (d_model, spec.n_experts), dtype),
        "w_gate": normal_init(ks[1], (e, d_model, f), dtype),
        "w_up": normal_init(ks[2], (e, d_model, f), dtype),
        "w_down": normal_init(ks[3], (e, f, d_model), dtype),
    }
    if spec.n_shared:
        p["shared"] = init_mlp(ks[4], d_model, spec.n_shared * f, act, dtype)
    return p


def _gates(probs: Array, spec: MoESpec):
    """Top-k over all experts: (gates, expert ids), each (..., k)."""
    top_p, top_i = jax.lax.top_k(probs, spec.top_k)
    if spec.norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_i


def balance_loss(probs: Array, top_i: Array, n_experts: int) -> Array:
    """Per sequence E/(k S) sum_i count_i mean_s p_{s,i}, averaged over
    sequences. probs: (B, S, E); top_i: (B, S, k)."""
    s, k = top_i.shape[1], top_i.shape[2]
    counts = jax.nn.one_hot(top_i, n_experts, dtype=jnp.float32).sum((1, 2))
    aux = n_experts / (k * s) * jnp.sum(counts * probs.mean(1), axis=-1)
    return aux.mean()


def _passes(n_experts: int, n_held: int, n: int):
    """(passes, rows per pass) over n sorted assignments: each pass takes
    twice the rows the held experts get under even routing, so one pass
    is the rule and further passes run only for a skewed batch."""
    n_pass = max(1, min(n, n_experts // (2 * n_held)))
    return n_pass, -(-n // n_pass)


def _routed(params: dict, x: Array, first, *, spec: MoESpec, act: str):
    """The routed part of the experts the weights hold, ``first`` to
    ``first`` + len(w_gate), for every assignment routed to them.
    x: (B, S, d) -> (y, balance loss, rows of each held expert).

    The assignments, sorted by held expert (those of experts not held
    last), are taken in passes of a fixed number of rows; a pass that
    starts past the held rows is skipped, so memory and work follow the
    rows routed here while every one of them is computed."""
    b, s, d = x.shape
    t, k, e = b * s, spec.top_k, params["w_gate"].shape[0]
    inner = {"swiglu": jax.nn.silu, "geglu": jax.nn.gelu}[act]
    w = {n: params[n].astype(x.dtype) for n in ("w_gate", "w_up", "w_down")}
    xt = x.reshape(t, d)
    logits = jnp.dot(xt.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, top_i = _gates(probs, spec)

    local = top_i.reshape(t * k) - first
    group = jnp.where((local >= 0) & (local < e), local, e)  # e: not held
    sizes = jnp.bincount(group, length=e + 1)[:e]
    rows = sizes.sum()
    n_pass, cap = _passes(spec.n_experts, e, t * k)
    order = jnp.argsort(group, stable=True)
    order = jnp.pad(order, (0, n_pass * cap - t * k))
    gate = gates.reshape(t * k)[order].astype(x.dtype)
    ends = jnp.cumsum(sizes)

    def one_pass(y, start):
        def run(y):
            idx = jax.lax.dynamic_slice_in_dim(order, start, cap)
            window = (jnp.clip(ends, start, start + cap)
                      - jnp.clip(ends - sizes, start, start + cap))
            live = (start + jnp.arange(cap) < rows)[:, None]
            # rows past ragged_dot's groups are not defined (its
            # transpose's too): select them away, never multiply by them
            g = jnp.where(live[:, 0],
                          jax.lax.dynamic_slice_in_dim(gate, start, cap), 0)
            tok = idx // k
            xs = jnp.where(live, xt[tok], 0)
            with jax.named_scope("moe_experts"):
                hg = jax.lax.ragged_dot(xs, w["w_gate"], window)
                hu = jax.lax.ragged_dot(xs, w["w_up"], window)
                h = inner(hg.astype(jnp.float32)).astype(x.dtype) * hu
                out = jax.lax.ragged_dot(h * g[:, None], w["w_down"],
                                         window)
            return y.at[tok].add(jnp.where(live, out, 0))
        return jax.lax.cond(start < rows, run, lambda y: y, y), None

    y, _ = jax.lax.scan(jax.checkpoint(one_pass, prevent_cse=False),
                        jnp.zeros((t, d), x.dtype),
                        jnp.arange(n_pass) * cap)
    aux = balance_loss(probs.reshape(b, s, -1), top_i.reshape(b, s, k),
                       spec.n_experts)
    return y.reshape(b, s, d), aux, sizes


def apply_held_moe(params: dict, x: Array, spec: MoESpec, act: str, *,
                   expert_map: Optional[Callable] = None):
    """x: (B, S, d) -> (out, stats): the held experts' part of the routed
    result plus the shared experts; stats hold the balance loss ``aux``,
    ``moe_rows`` (assignments computed) and ``moe_max_rows`` (the most
    rows of one held expert).

    ``expert_map`` (from the launch layer, under a mesh) runs the routed
    part once per device on that device's tokens and experts and sums
    the parts; without it the weights' experts run here."""
    routed = {n: params[n] for n in ("router", "w_gate", "w_up", "w_down")}
    run = partial(_routed, spec=spec, act=act)
    with jax.named_scope("moe"):
        if expert_map is None:
            y, aux, sizes = run(routed, x, spec.held_first)
            rows, most = sizes.sum(), sizes.max()
        else:
            y, aux, rows, most = expert_map(run, routed, x, spec.held_first)
        if spec.n_shared:
            y = y + apply_mlp(params["shared"], x, act)
    return y, {"aux": aux, "moe_rows": rows.astype(jnp.int32),
               "moe_max_rows": most.astype(jnp.int32)}
