"""Plain reference of one rwkv6 ("Finch", arXiv:2404.05892) training step.

Forward pass, next-token cross-entropy, gradients and AdamW, written out
in jax.numpy with the wkv recurrence run one token at a time, as the
paper states it (per head, state S of dk x dv):

    y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T,   w_t = exp(-exp(w0 + lora_w(x_t)))

It imports nothing of the program under test: it is handed the
benchmark's own weights (weights.py) and batches, and the configuration
file's sizes and optimizer settings. Departures from the published model,
all of them stated by the configuration as run: the wkv output's group
norm uses eps 1e-5, AdamW's weight decay covers every leaf, and its
learning rate warms up as lr * min(1, (t + 1) / warmup) at update t.

Memory: the batch is taken in blocks of rows (the loss is a sum over
tokens, so the gradients of the blocks add up), and the recurrence keeps
its state only at chunk boundaries for the backward pass, recomputing
each chunk: the arithmetic is that of the plain recurrence.

In float32 every matrix product runs at ``highest`` precision. The same
code in bfloat16 (weights, activations and optimizer state) is the
lower-precision control.
"""
from __future__ import annotations

import json
from functools import lru_cache, partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _shift(x):
    """Token shift: position t sees x_{t-1}, position 0 sees zeros."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _wkv(r, k, v, w, u, chunk: int):
    """r, k, v, w: (B, S, H, D); u: (H, D). Returns y: (B, S, H, D)."""
    b, s, h, d = r.shape

    def one(state, inp):
        r_t, k_t, v_t, w_t = inp
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum("bhk,bhkv->bhv", r_t, state + u[..., None] * kv)
        return w_t[..., None] * state + kv, y

    @jax.checkpoint
    def run_chunk(state, xs):
        return jax.lax.scan(one, state, xs)

    def t_major(a):  # (B,S,H,D) -> (S/chunk, chunk, B, H, D)
        return a.transpose(1, 0, 2, 3).reshape(s // chunk, chunk, b, h, d)

    state0 = jnp.zeros((b, h, d, d), r.dtype)
    _, ys = jax.lax.scan(run_chunk, state0,
                         (t_major(r), t_major(k), t_major(v), t_major(w)))
    return ys.reshape(s, b, h, d).transpose(1, 0, 2, 3)


def _time_mix(p, x, cfg):
    b, s, dm = x.shape
    hd = cfg["head_size"]
    h = dm // hd
    dx = _shift(x) - x
    lora = jnp.tanh((x + dx * p["mix_x"]) @ p["mix_w1"])
    lora = lora.reshape(b, s, 5, -1)
    mix = p["mix_mu"] + jnp.einsum("bsfm,fmd->bsfd", lora, p["mix_w2"])
    xr, xk, xv, xg, xw = (x + dx * mix[:, :, i] for i in range(5))
    r = (xr @ p["wr"]).reshape(b, s, h, hd)
    k = (xk @ p["wk"]).reshape(b, s, h, hd)
    v = (xv @ p["wv"]).reshape(b, s, h, hd)
    g = jax.nn.silu(xg @ p["wg"])
    w = jnp.exp(-jnp.exp(p["w0"] + jnp.tanh(xw @ p["dw1"]) @ p["dw2"]))
    y = _wkv(r, k, v, w.reshape(b, s, h, hd), p["bonus_u"],
             cfg["reference"]["scan_chunk"])
    mu = y.mean(-1, keepdims=True)
    var = jnp.square(y - mu).mean(-1, keepdims=True)
    y = ((y - mu) / jnp.sqrt(var + cfg["group_norm_epsilon"])
         ).reshape(b, s, dm)
    y = y * p["ln_x_scale"] + p["ln_x_bias"]
    return (y * g) @ p["wo"]


def _channel_mix(p, x):
    dx = _shift(x) - x
    kk = jnp.square(jax.nn.relu((x + dx * p["cmu_k"]) @ p["ck"]))
    rr = jax.nn.sigmoid((x + dx * p["cmu_r"]) @ p["cr"])
    return rr * (kk @ p["cv"])


def _layers(params, n_layers: int) -> List[dict]:
    (stage,) = params["stages"]
    if n_layers == 1:
        return [stage["L0"]]
    return [jax.tree.map(lambda t, i=i: t[i], stage["L0"])
            for i in range(n_layers)]


def xent_sum(params, tokens, labels, cfg):
    """Summed next-token cross-entropy of a block of rows."""
    eps = cfg["layer_norm_epsilon"]
    x = params["embed"][tokens]
    for lp in _layers(params, cfg["num_hidden_layers"]):
        x = x + _time_mix(lp["mixer"], _ln(x, lp["ln1"], eps), cfg)
        x = x + _channel_mix(lp["mlp"], _ln(x, lp["ln2"], eps))
    logits = _ln(x, params["final_norm"], eps) @ params["lm_head"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked).astype(jnp.float32))


def _norms(tree) -> Dict[str, jax.Array]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/" + "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                           for k in path):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in flat}


def _adamw(opt, params, grads, m, v, t):
    """One AdamW update (t: 1-based update count). Returns the new
    params, m, v and the clipped gradients as the update used them."""
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in leaves))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
    grads = jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)
    b1, b2 = opt["b1"], opt["b2"]
    lr = opt["lr"] * min(1.0, (t + 1) / max(opt["warmup_steps"], 1))
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g),
                     v, grads)

    def upd(p, m_, v_):
        mhat = m_ / (1 - b1 ** t)
        vhat = v_ / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                         + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, m, v), m, v, grads


@lru_cache(maxsize=None)
def _programs(cfg_json: str, dtype_name: str):
    cfg = json.loads(cfg_json)
    precision = "highest" if dtype_name == "float32" else "default"

    def at(precision_fn):
        def run(*args):
            with jax.default_matmul_precision(precision):
                return precision_fn(*args)
        return run

    return {
        "grad_block": jax.jit(at(jax.value_and_grad(
            partial(xent_sum, cfg=cfg)))),
        "add": jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b)),
        "update": jax.jit(at(partial(_adamw, cfg["optimizer"])),
                          static_argnums=(4,)),
        "delta": jax.jit(lambda a, b: _norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b))),
        "norms": jax.jit(_norms),
    }


def train_steps(cfg: dict, params0, batches: List[dict], *,
                dtype=jnp.float32) -> dict:
    """Runs len(batches) reference steps from ``params0``. Returns the
    loss of each step, the per-leaf norms of the first step's clipped
    gradients, and the per-leaf norms of the change of the parameters
    over all steps, as host floats."""
    rows = cfg["reference"]["rows_per_block"]
    fn = _programs(json.dumps(cfg, sort_keys=True), jnp.dtype(dtype).name)
    params = jax.tree.map(lambda x: x.astype(dtype), params0)
    start = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    out = {"losses": []}
    for t, batch in enumerate(batches, start=1):
        toks, labs = batch["tokens"], batch["labels"]
        n = toks.size
        loss, grads = 0.0, None
        for i in range(0, toks.shape[0], rows):
            s, g = fn["grad_block"](params, jnp.asarray(toks[i:i + rows]),
                                    jnp.asarray(labs[i:i + rows]))
            loss += float(s)
            grads = g if grads is None else fn["add"](grads, g)
        grads = jax.tree.map(lambda g: (g / n).astype(dtype), grads)
        params, m, v, used = fn["update"](params, grads, m, v, t)
        out["losses"].append(loss / n)
        if t == 1:
            out["grad_norms"] = {k: float(x) for k, x in
                                 fn["norms"](used).items()}
    out["update_norms"] = {k: float(x) for k, x in
                           fn["delta"](params, start).items()}
    return out


def median(values) -> float:
    return float(np.median(np.asarray(list(values), np.float64)))
