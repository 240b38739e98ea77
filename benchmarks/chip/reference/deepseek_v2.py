"""Plain reference of DeepSeek-V2 (arXiv:2405.04434) training steps, at one
chip's expert share.

Forward pass, next-token cross-entropy plus the balance loss, gradients
and AdamW, written out in jax.numpy from the configuration file's sizes
(the equations are those of ``repro/configs/deepseek_v2_lite.py``):

    attention (MLA, no query low-rank): q = x Wq; [c | k_pe] = x W_kva;
      c <- RMSNorm(c); [k_nope | v] = c W_kvb; k_pe one key for every
      head; YaRN rope on q_pe and k_pe; o = softmax(q.k m^2 / sqrt(dqk)
      + causal mask) v; out = o Wo
    MoE: p = softmax(x W_r) over every published expert; top-k of p,
      gates g = p (renormalised only where norm_topk_prob); y = sum over
      the held experts e in the top-k of g_e SwiGLU_e(x) + SwiGLU_shared(x)
    balance: alpha E/(K S) sum_i count_i mean_s p_{s,i} per sequence and
      layer, averaged over the sequences, summed over the layers

It imports nothing of the program under test: it is handed the
benchmark's own weights (weights.py) and batches. Departures from the
published model, all of them stated by the configuration as run: only
the held experts' part of the routed sum is computed (the chip's share;
the router keeps every output), the rope dims rotate as halves, AdamW's
weight decay covers every leaf, and its learning rate warms up as
lr * min(1, (t + 1) / warmup) at update t.

Plainness over speed: attention scores every (query, key) pair under
the full causal mask; each held expert runs over every token, its output
times its gate where it is in the token's top-k and zero elsewhere (no
sorting, no grouping, no capacity). Memory: the batch is taken in blocks
of ``rows_per_block`` rows (the loss is a sum over tokens, so the
gradients of the blocks add up), each layer is rematerialized, and the
attention takes ``query_block`` query rows at a time, each block
rematerialized too: the arithmetic is unchanged.

In float32 every matrix product runs at ``highest`` precision. The same
code in bfloat16 (weights, activations and optimizer state) is the
lower-precision control.
"""
from __future__ import annotations

import json
import math
from functools import lru_cache, partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference.rwkv6 import _adamw, _norms, median  # noqa


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg) -> np.ndarray:
    """YaRN's inverse frequencies of the rope dims, in float64."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    y = cfg["rope_scaling"]
    extra = base ** (-np.arange(0, dim, 2) / dim)
    inter = extra / y["factor"]

    def corr(n):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (n * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * ramp + extra * (1 - ramp)


def _rope(x, cfg):
    """x: (B, S, H, 64); the halves of the last axis rotate as pairs."""
    y = cfg["rope_scaling"]
    m = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"],
                                                    y["mscale_all_dim"])
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(cfg), jnp.float32)
    cos = (jnp.cos(ang) * m)[None, :, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * m)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, scale, block):
    """Causal softmax attention, ``block`` query rows at a time.
    q, k: (B, S, H, Dqk); v: (B, S, H, Dv)."""
    b, s, h, _ = q.shape
    nb = s // block
    kpos = jnp.arange(s)

    @jax.checkpoint
    def rows(args):
        qb, start = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        qpos = start + jnp.arange(block)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)

    qs = q.reshape(b, nb, block, h, -1).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(rows, (qs, jnp.arange(nb) * block))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, -1)


def _attention(p, x, cfg):
    b, s, _ = x.shape
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = (x @ p["wq"]).reshape(b, s, h, dn + dr)
    c, k_pe = jnp.split(x @ p["kv_a"], [cfg["kv_lora_rank"]], axis=-1)
    c = _rms(c, p["kv_a_norm"]["scale"], cfg["rms_norm_eps"])
    k_nope, v = jnp.split((c @ p["kv_b"]).reshape(b, s, h, dn + dv), [dn],
                          axis=-1)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg)], -1)
    k_pe = _rope(k_pe[:, :, None, :], cfg)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, h, dr))], -1)
    y = cfg["rope_scaling"]
    scale = _mscale(y["factor"], y["mscale_all_dim"]) ** 2 \
        / math.sqrt(dn + dr)
    o = _attend(q, k, v, scale, min(cfg["reference"]["query_block"], s))
    return o.reshape(b, s, h * dv) @ p["wo"]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def routing(p, x, cfg):
    """Router probabilities (B, S, E) and each token's top-k ids."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    return probs, jax.lax.top_k(probs, cfg["num_experts_per_tok"])


def _moe(p, x, cfg):
    """The held experts' part of the routed sum plus the shared experts,
    and each sequence's balance loss (B,)."""
    n_exp, k = cfg["published"]["n_routed_experts"], \
        cfg["num_experts_per_tok"]
    probs, (top_p, top_i) = routing(p, x, cfg)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    sh = p["shared"]
    y = _swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])
    for e in range(cfg["n_routed_experts"]):
        gate = jnp.sum(jnp.where(top_i == e, top_p, 0), -1)
        y = y + gate[..., None] * _swiglu(x, p["w_gate"][e], p["w_up"][e],
                                          p["w_down"][e])
    counts = jax.nn.one_hot(top_i, n_exp, dtype=x.dtype).sum((1, 2))
    aux = n_exp / (k * x.shape[1]) * jnp.sum(counts * probs.mean(1), -1)
    return y, aux


def _layer(lp, x, cfg, moe: bool):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(lp["mixer"], _rms(x, lp["ln1"]["scale"], eps), cfg)
    h = _rms(x, lp["ln2"]["scale"], eps)
    if not moe:
        m = lp["mlp"]
        return x + _swiglu(h, m["w_gate"], m["w_up"], m["w_down"]), 0.0
    y, aux = _moe(lp["mlp"], h, cfg)
    return x + y, aux


def layers(params, cfg):
    """(layer params, is MoE) in order: the dense layers, then the MoE
    stage's stacked layers."""
    dense, moe = params["stages"]
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return [(dense["L0"], False)] + [
        (jax.tree.map(lambda t, i=i: t[i], moe["L0"]), True)
        for i in range(n_moe)]


def objective_sum(params, tokens, labels, cfg):
    """Summed next-token cross-entropy of a block of rows, plus alpha
    times the block's balance losses scaled so that the blocks' sums,
    over the batch's tokens, give alpha times the mean over sequences."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    aux = jnp.zeros(tokens.shape[:1], x.dtype)
    for lp, moe in layers(params, cfg):
        x, a = jax.checkpoint(partial(_layer, cfg=cfg, moe=moe))(lp, x)
        aux = aux + a
    logits = _rms(x, params["final_norm"]["scale"], eps) @ params["lm_head"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    xent = jnp.sum((lse - picked).astype(jnp.float32))
    return xent + cfg["aux_loss_alpha"] * tokens.shape[1] * jnp.sum(
        aux.astype(jnp.float32))


def top_sets(params, tokens, cfg) -> List[np.ndarray]:
    """Each MoE layer's top-k expert ids (B, S, k) of one forward pass,
    sorted along k, as host arrays (a diagnostic of routing agreement)."""
    out = []
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x = params["embed"][jnp.asarray(tokens)]
        for lp, moe in layers(params, cfg):
            if moe:
                h = _rms(x + _attention(lp["mixer"], _rms(
                    x, lp["ln1"]["scale"], eps), cfg), lp["ln2"]["scale"],
                    eps)
                _, (_, top_i) = routing(lp["mlp"], h, cfg)
                out.append(np.sort(np.asarray(top_i), -1))
            x, _ = _layer(lp, x, cfg, moe)
    return out


@lru_cache(maxsize=None)
def _programs(cfg_json: str, dtype_name: str):
    cfg = json.loads(cfg_json)
    precision = "highest" if dtype_name == "float32" else "default"

    def at(fn):
        def run(*args):
            with jax.default_matmul_precision(precision):
                return fn(*args)
        return run

    grad = jax.value_and_grad(partial(objective_sum, cfg=cfg))

    def accumulate(params, acc, tokens, labels):
        s, g = grad(params, tokens, labels)
        return s, jax.tree.map(jnp.add, acc, g)

    def update(params, grads, m, v, t):
        params, m, v, used = _adamw(cfg["optimizer"], params, grads, m, v, t)
        return params, m, v, _norms(used)

    return {
        "accumulate": jax.jit(at(accumulate), donate_argnums=(1,)),
        "update": jax.jit(at(update), static_argnums=(4,),
                          donate_argnums=(0, 1, 2, 3)),
        "scale": jax.jit(lambda g, n: jax.tree.map(lambda x: x / n, g),
                         donate_argnums=(0,)),
        "delta": jax.jit(lambda a, b: _norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b))),
    }


def train_steps(cfg: dict, params0, batches: List[dict], *,
                dtype=jnp.float32) -> dict:
    """Runs len(batches) reference steps from ``params0`` (whose device
    buffers it takes over). Returns the loss of each step, the per-leaf
    norms of the first step's clipped gradients, and the per-leaf norms
    of the change of the parameters over all steps, as host floats."""
    rows = cfg["reference"]["rows_per_block"]
    fn = _programs(json.dumps(cfg, sort_keys=True), jnp.dtype(dtype).name)
    params = jax.tree.map(lambda x: x.astype(dtype), params0)
    del params0
    start = jax.device_get(params)  # on the host, out of the device's way
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    out = {"losses": []}
    for t, batch in enumerate(batches, start=1):
        toks, labs = batch["tokens"], batch["labels"]
        loss, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
        for i in range(0, toks.shape[0], rows):
            s, grads = fn["accumulate"](params, grads,
                                        jnp.asarray(toks[i:i + rows]),
                                        jnp.asarray(labs[i:i + rows]))
            loss += float(s)
        grads = fn["scale"](grads, toks.size)
        params, m, v, norms = fn["update"](params, grads, m, v, t)
        out["losses"].append(loss / toks.size)
        if t == 1:
            out["grad_norms"] = {k: float(x) for k, x in norms.items()}
    del m, v
    out["update_norms"] = {k: float(x) for k, x in
                           fn["delta"](params, start).items()}
    return out
