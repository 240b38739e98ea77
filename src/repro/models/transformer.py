"""Unified decoder-only model over heterogeneous layer stacks.

An architecture is a sequence of stages; each stage is a *superblock*
(tuple of LayerSpec) repeated R times. Superblocks with R > 1 are executed
with ``jax.lax.scan`` over stacked parameters (compile time O(1) in depth)
and wrapped in ``jax.checkpoint`` for training (remat).

Three modes share one code path:
  - train:   full sequence, no cache, returns loss-ready logits
  - prefill: full sequence, writes the decode cache
  - decode:  single token at scalar position ``pos`` against the cache
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec, Stage
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (act_fn, apply_mlp, apply_norm, init_mlp,
                                 init_norm, normal_init, sinusoidal_pos_emb,
                                 softcap)

Array = jax.Array

VOCAB_PAD = 256  # pad vocab to a multiple of this (TP divisibility)


def padded_vocab(v: int) -> int:
    return (v + VOCAB_PAD - 1) // VOCAB_PAD * VOCAB_PAD


@dataclass(frozen=True)
class RunConfig:
    """Static runtime knobs (perf-iteration surface)."""

    attn_impl: str = "chunked"  # chunked | tri | naive
    chunk_q: int = 512
    chunk_kv: int = 1024
    mamba_chunk: int = 128
    rwkv_chunk: int = 64
    remat: bool = True
    loss_chunk: int = 1024  # seq-chunked vocab xent (rematerialized)
    head_pad: int = 1  # pad head counts to this multiple (TP divisibility)
    param_dtype: Any = jnp.bfloat16
    cache_dtype: Any = jnp.bfloat16
    mla_absorb: bool = True
    scan_stages: bool = True  # False unrolls layers (perf/compile comparison)
    # Injected by the launch layer: shard(x, partition_tuple) -> x
    shard: Optional[Callable] = None
    # Injected by the launch layer: expert_map(run, params, x, first)
    # runs an MoE layer's routed experts once per device (moe.py)
    expert_map: Optional[Callable] = None


# ===========================================================================
# Init
# ===========================================================================


def _init_block(cfg: ArchConfig, spec: LayerSpec, key, dtype,
                head_pad: int = 1) -> dict:
    ks = jax.random.split(key, 4)
    p = {"ln1": init_norm(cfg.norm, cfg.d_model, dtype),
         "ln2": init_norm(cfg.norm, cfg.d_model, dtype)}
    if spec.kind == "attn":
        p["mixer"] = attn_mod.init_attn(ks[0], cfg.d_model, spec.attn, dtype,
                                        head_pad)
    elif spec.kind == "mamba":
        p["mixer"] = ssm_mod.init_mamba_full(ks[0], cfg.d_model, spec.mamba,
                                             dtype)
    elif spec.kind == "rwkv":
        p["mixer"] = ssm_mod.init_rwkv(ks[0], cfg.d_model, spec.rwkv, dtype)
    else:
        raise ValueError(spec.kind)

    if spec.mlp.kind == "dense":
        p["mlp"] = init_mlp(ks[1], cfg.d_model, spec.mlp.d_ff, spec.mlp.act,
                            dtype)
    elif spec.mlp.kind == "moe":
        p["mlp"] = moe_mod.init_moe(ks[1], cfg.d_model, spec.mlp.moe,
                                    spec.mlp.act, dtype)
    elif spec.mlp.kind == "none":
        if spec.kind == "rwkv":
            p["mlp"] = ssm_mod.init_rwkv_channel(ks[1], cfg.d_model,
                                                 spec.rwkv, dtype)
        else:
            p["mlp"] = {}
    return p


def _init_superblock(cfg, stage: Stage, key, dtype,
                     head_pad: int = 1) -> dict:
    ks = jax.random.split(key, len(stage.block))
    return {f"L{i}": _init_block(cfg, spec, ks[i], dtype, head_pad)
            for i, spec in enumerate(stage.block)}


def init_params(cfg: ArchConfig, key, rc: RunConfig = RunConfig()) -> dict:
    dtype = rc.param_dtype
    n_stage = len(cfg.stages)
    ks = jax.random.split(key, n_stage + 3)
    vp = padded_vocab(cfg.vocab_size)
    params = {"embed": normal_init(ks[0], (vp, cfg.d_model), dtype),
              "final_norm": init_norm(cfg.norm, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = normal_init(ks[1], (cfg.d_model, vp), dtype)
    stages = []
    for i, stage in enumerate(cfg.stages):
        if stage.repeat == 1:
            stages.append(_init_superblock(cfg, stage, ks[2 + i], dtype,
                                           rc.head_pad))
        else:
            stages.append(jax.vmap(
                lambda k, st=stage, kk=None: _init_superblock(
                    cfg, st, k, dtype, rc.head_pad))(
                jax.random.split(ks[2 + i], stage.repeat)))
    params["stages"] = stages
    return params


# ===========================================================================
# Cache init
# ===========================================================================


def _init_layer_cache(cfg, spec: LayerSpec, batch: int, max_len: int, rc):
    cd = rc.cache_dtype
    if spec.kind == "attn":
        a = spec.attn
        if a.mla is not None:
            return {"c_kv": jnp.zeros((batch, max_len, a.mla.kv_lora_rank),
                                      cd),
                    "k_rope": jnp.zeros((batch, max_len, a.mla.qk_rope_dim),
                                        cd)}
        nkv = a.n_kv_heads
        if a.n_kv_heads == a.n_heads:  # MHA: kv padded in lockstep with q
            nkv = attn_mod.padded_heads(a.n_kv_heads, rc.head_pad)
        return {"k": jnp.zeros((batch, max_len, nkv, a.head_dim), cd),
                "v": jnp.zeros((batch, max_len, nkv, a.head_dim), cd)}
    if spec.kind == "mamba":
        di = spec.mamba.d_inner(cfg.d_model)
        return {"conv": jnp.zeros((batch, spec.mamba.d_conv - 1, di), cd),
                "ssm": jnp.zeros((batch, di, spec.mamba.d_state),
                                 jnp.float32)}
    if spec.kind == "rwkv":
        h = cfg.d_model // spec.rwkv.head_dim
        return {"shift_tm": jnp.zeros((batch, cfg.d_model), cd),
                "shift_cm": jnp.zeros((batch, cfg.d_model), cd),
                "wkv": jnp.zeros((batch, h, spec.rwkv.head_dim,
                                  spec.rwkv.head_dim), jnp.float32)}
    raise ValueError(spec.kind)


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               rc: RunConfig = RunConfig()):
    caches = []
    for stage in cfg.stages:
        block = {f"L{i}": _init_layer_cache(cfg, spec, batch, max_len, rc)
                 for i, spec in enumerate(stage.block)}
        if stage.repeat == 1:
            caches.append(block)
        else:
            caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (stage.repeat,) + x.shape),
                block))
    return caches


# ===========================================================================
# Apply
# ===========================================================================


def _apply_mixer(cfg, spec: LayerSpec, rc: RunConfig, params, x, *, mode,
                 positions, pos, cache):
    if spec.kind == "attn":
        a = spec.attn
        if a.mla is not None:
            with jax.named_scope("mla"):
                if mode == "decode":
                    return attn_mod.mla_decode(params, x, a, pos=pos,
                                               cache=cache,
                                               absorb=rc.mla_absorb)
                return attn_mod.mla_forward(
                    params, x, a, positions=positions, impl=rc.attn_impl,
                    chunk_q=rc.chunk_q, chunk_kv=rc.chunk_kv, cache=cache,
                    shard=rc.shard)
        if mode == "decode":
            return attn_mod.gqa_decode(params, x, a, pos=pos, cache=cache)
        return attn_mod.gqa_forward(params, x, a, positions=positions,
                                    impl=rc.attn_impl, chunk_q=rc.chunk_q,
                                    chunk_kv=rc.chunk_kv, cache=cache,
                                    shard=rc.shard)
    if spec.kind == "mamba":
        if mode == "decode":
            return ssm_mod.mamba_decode(params, x, spec.mamba, cfg.d_model,
                                        cache=cache)
        return ssm_mod.mamba_forward(params, x, spec.mamba, cfg.d_model,
                                     chunk=rc.mamba_chunk, cache=cache)
    if spec.kind == "rwkv":
        return ssm_mod.rwkv_time_mix(params, x, spec.rwkv,
                                     chunk=rc.rwkv_chunk, cache=cache,
                                     mode=mode)
    raise ValueError(spec.kind)


def _no_stats() -> dict:
    """A layer's training counters: the MoE balance loss, the expert rows
    computed (summed over layers) and the most rows of one expert (the
    largest over layers)."""
    return {"aux": jnp.zeros((), jnp.float32),
            "moe_rows": jnp.zeros((), jnp.int32),
            "moe_max_rows": jnp.zeros((), jnp.int32)}


def _add_stats(a: dict, b: dict) -> dict:
    return {"aux": a["aux"] + b["aux"],
            "moe_rows": a["moe_rows"] + b["moe_rows"],
            "moe_max_rows": jnp.maximum(a["moe_max_rows"], b["moe_max_rows"])}


def _apply_block(cfg, spec: LayerSpec, rc, params, x, *, mode, positions,
                 pos, cache):
    new_cache = {} if cache is not None else None
    h = apply_norm(cfg.norm, params["ln1"], x, cfg.norm_eps)
    mix_out, mix_cache = _apply_mixer(cfg, spec, rc, params["mixer"], h,
                                      mode=mode, positions=positions, pos=pos,
                                      cache=cache)
    x = x + mix_out
    stats = _no_stats()
    h = apply_norm(cfg.norm, params["ln2"], x, cfg.norm_eps)
    if spec.mlp.kind == "dense":
        x = x + apply_mlp(params["mlp"], h, spec.mlp.act)
        mlp_cache = None
    elif spec.mlp.kind == "moe":
        y, stats = moe_mod.apply_held_moe(params["mlp"], h, spec.mlp.moe,
                                          spec.mlp.act,
                                          expert_map=rc.expert_map)
        x = x + y
        mlp_cache = None
    elif spec.kind == "rwkv":
        y, mlp_cache = ssm_mod.rwkv_channel_mix(params["mlp"], h, cache=cache,
                                                mode=mode)
        x = x + y
    else:
        mlp_cache = None
    if cache is not None:
        new_cache = dict(mix_cache or {})
        if mlp_cache:
            new_cache.update(mlp_cache)
    if rc.shard is not None:
        x = rc.shard(x, ("data", None, None))
    return x, new_cache, stats


def _apply_superblock(cfg, stage: Stage, rc, params, x, *, mode, positions,
                      pos, cache):
    new_cache = {} if cache is not None else None
    stats = _no_stats()
    for i, spec in enumerate(stage.block):
        li = f"L{i}"
        x, c_new, st = _apply_block(
            cfg, spec, rc, params[li], x, mode=mode, positions=positions,
            pos=pos, cache=None if cache is None else cache[li])
        if cache is not None:
            new_cache[li] = c_new
        stats = _add_stats(stats, st)
    return x, new_cache, stats


def _apply_stage(cfg, stage: Stage, rc, params, x, *, mode, positions, pos,
                 cache):
    if stage.repeat == 1 or not rc.scan_stages:
        if stage.repeat == 1:
            return _apply_superblock(cfg, stage, rc, params, x, mode=mode,
                                     positions=positions, pos=pos,
                                     cache=cache)
        # unrolled path (scan_stages=False): index the stacked params
        stats = _no_stats()
        new_cache = {} if cache is not None else None
        caches_out = []
        for r in range(stage.repeat):
            p_r = jax.tree.map(lambda t: t[r], params)
            c_r = None if cache is None else jax.tree.map(lambda t: t[r],
                                                          cache)
            x, c_new, st = _apply_superblock(cfg, stage, rc, p_r, x,
                                             mode=mode, positions=positions,
                                             pos=pos, cache=c_r)
            caches_out.append(c_new)
            stats = _add_stats(stats, st)
        if cache is not None:
            new_cache = jax.tree.map(lambda *ts: jnp.stack(ts), *caches_out)
        return x, new_cache, stats

    def body(carry, xs):
        x_, stats_ = carry
        if cache is None:
            p_r, c_r = xs, None
        else:
            p_r, c_r = xs
        x_, c_new, st = _apply_superblock(cfg, stage, rc, p_r, x_,
                                          mode=mode, positions=positions,
                                          pos=pos, cache=c_r)
        return (x_, _add_stats(stats_, st)), c_new

    if rc.remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)
    xs = params if cache is None else (params, cache)
    (x, stats), new_cache = jax.lax.scan(body, (x, _no_stats()), xs)
    return x, new_cache, stats


def _embed(cfg: ArchConfig, params, tokens, frontend, positions):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embed:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.d_model)).astype(x.dtype)
    if frontend is not None:
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
    if cfg.pos_emb == "sinusoidal":
        pe = sinusoidal_pos_emb(positions, cfg.d_model)
        x = (x.astype(jnp.float32) + pe).astype(x.dtype)
    return x


def _logits(cfg: ArchConfig, params, x, rc):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    if rc.shard is not None:
        logits = rc.shard(logits, ("data", None, "model"))
    logits = softcap(logits, cfg.logit_softcap)
    vp = padded_vocab(cfg.vocab_size)
    if vp != cfg.vocab_size:  # mask padded vocab rows
        valid = jnp.arange(vp) < cfg.vocab_size
        logits = jnp.where(valid, logits, jnp.asarray(-1e30, logits.dtype))
    return logits


def forward(cfg: ArchConfig, rc: RunConfig, params, tokens, *,
            frontend=None, mode: str = "train", caches=None, pos=None):
    """tokens: (B,S) [decode: (B,1)]. Returns (hidden, new_caches, stats) —
    hidden is the final-norm output; entry points project to logits only
    where needed (last position for prefill; seq-chunked for the loss)."""
    b, s = tokens.shape
    if mode == "decode":
        positions = None
        x = _embed(cfg, params, tokens, None,
                   jnp.broadcast_to(pos, (b, 1)) if cfg.pos_emb ==
                   "sinusoidal" else pos)
    else:
        total = s + (frontend.shape[1] if frontend is not None else 0)
        positions = jnp.arange(total)
        x = _embed(cfg, params, tokens, frontend, positions[None])
    if rc.shard is not None:
        x = rc.shard(x, ("data", None, None))
    new_caches = [] if caches is not None else None
    stats = _no_stats()
    for i, stage in enumerate(cfg.stages):
        x, c_new, st = _apply_stage(
            cfg, stage, rc, params["stages"][i], x, mode=mode,
            positions=positions, pos=pos,
            cache=None if caches is None else caches[i])
        stats = _add_stats(stats, st)
        if caches is not None:
            new_caches.append(c_new)
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    return x, new_caches, stats


# ===========================================================================
# Entry points
# ===========================================================================


def _chunked_xent(cfg: ArchConfig, rc: RunConfig, params, x, labels):
    """Seq-chunked vocab cross-entropy: never materializes (B,S,V) logits.

    Each chunk's logits are recomputed in the backward pass
    (jax.checkpoint), bounding live memory to (B, C, V/tp) — essential for
    262k-vocab archs at 1M tokens/step. Returns (sum_xent, sum_mask)."""
    b, s, d = x.shape
    c = min(rc.loss_chunk, s)
    nc = math.ceil(s / c)
    pad = nc * c - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    xc = x.reshape(b, nc, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, c).transpose(1, 0, 2)

    def chunk(x_c, lab_c):
        logits = _logits(cfg, params, x_c, rc)
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, jnp.maximum(lab_c, 0)[..., None],
                                 axis=-1)[..., 0]
        mask = (lab_c >= 0).astype(jnp.float32)
        return ((lse - ll) * mask).sum(), mask.sum()

    chunk = jax.checkpoint(chunk, prevent_cse=False)

    def body(carry, xs):
        se, sm = carry
        e, m = chunk(*xs)
        return (se + e, sm + m), None

    (sum_e, sum_m), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, lc))
    return sum_e, sum_m


def loss_fn(cfg: ArchConfig, rc: RunConfig, params, batch):
    """batch: tokens (B,S) int32, labels (B,S) int32 (-1 = masked),
    optional frontend_embeds (B,Nf,d). The MoE balance loss is weighted
    by the model's ``moe_aux_coef``."""
    frontend = batch.get("frontend_embeds")
    hidden, _, stats = forward(cfg, rc, params, batch["tokens"],
                             frontend=frontend, mode="train")
    nf = frontend.shape[1] if frontend is not None else 0
    hidden = hidden[:, nf:]  # token positions only
    sum_e, sum_m = _chunked_xent(cfg, rc, params, hidden, batch["labels"])
    xent = sum_e / jnp.maximum(sum_m, 1.0)
    loss = xent + cfg.moe_aux_coef * stats["aux"]
    return loss, {"xent": xent, **stats}


def prefill(cfg: ArchConfig, rc: RunConfig, params, tokens, caches, *,
            frontend=None):
    """Returns (last-position logits, filled caches). Logits are computed
    only for the final position (not the full sequence)."""
    hidden, caches, _ = forward(cfg, rc, params, tokens, frontend=frontend,
                                mode="prefill", caches=caches)
    logits = _logits(cfg, params, hidden[:, -1:], rc)
    return logits[:, -1], caches


def decode_step(cfg: ArchConfig, rc: RunConfig, params, tokens, pos, caches):
    """tokens (B,1), pos scalar int32. Returns (logits (B,V), caches)."""
    hidden, caches, _ = forward(cfg, rc, params, tokens, mode="decode",
                                caches=caches, pos=pos)
    logits = _logits(cfg, params, hidden, rc)
    return logits[:, -1], caches


# ===========================================================================
# Model wrapper + param accounting
# ===========================================================================


@dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    rc: RunConfig = RunConfig()

    def init(self, key):
        return init_params(self.cfg, key, self.rc)

    def init_cache(self, batch, max_len):
        return init_cache(self.cfg, batch, max_len, self.rc)

    def loss(self, params, batch):
        return loss_fn(self.cfg, self.rc, params, batch)

    def prefill(self, params, tokens, caches, frontend=None):
        return prefill(self.cfg, self.rc, params, tokens, caches,
                       frontend=frontend)

    def decode_step(self, params, tokens, pos, caches):
        return decode_step(self.cfg, self.rc, params, tokens, pos, caches)


def count_params(cfg: ArchConfig, rc: RunConfig = RunConfig()) -> int:
    shapes = jax.eval_shape(partial(init_params, cfg, rc=rc),
                            jax.random.key(0))
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes)
               if hasattr(l, "shape"))


def count_active_params(cfg: ArchConfig) -> int:
    """Params touched per token: total minus inactive routed experts (of
    a layer that holds some experts, those a token leaves, on average,
    when its top-k spreads evenly over all)."""
    total = count_params(cfg)
    inactive = 0
    for spec in cfg.layer_specs():
        if spec.mlp.kind == "moe":
            m = spec.mlp.moe
            gated = 3  # swiglu/geglu experts have 3 matrices
            per_expert = gated * cfg.d_model * m.d_expert
            inactive += m.n_held * (m.n_experts - m.top_k) * per_expert \
                // m.n_experts
    return total - inactive
