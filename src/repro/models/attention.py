"""Attention: GQA (chunked online-softmax), sliding-window, MLA, decode paths.

Layout note (sharding-driven, see EXPERIMENTS.md §Perf): train/prefill
attention runs in (B, H, S, D) layout with batch sharded over the dp
axes and (zero-padded) heads sharded over `model` — the Megatron head-TP
pattern. Head counts that do not divide the model axis (Qwen's 40,
Gemma's 4) are zero-padded at the parameter level (numerically exact:
padded v == 0). No dims are ever merged/reshaped across sharded
boundaries — merged (B*H) layouts were measured to defeat the SPMD
partitioner (it replicates instead of slicing; §Perf iterations 1-3).

Implementations
---------------
- ``chunked``: scan over KV chunks with running (max, sum, acc) — the
  flash-attention recurrence in pure jnp (O(S·Ck) peak memory, in the
  backward pass too: each chunk's scores are recomputed there).
- ``tri``: triangular (q-chunk, kv-chunk) pair iteration, j <= i — skips
  above-diagonal work entirely: half the FLOPs for causal shapes.
- ``naive``: materializes the full score matrix (perf-iteration baseline).
- ``window``: q-chunk scan over a dynamically sliced KV span —
  sub-quadratic; Gemma3 local layers (incl. long_500k).
- decode: single-position attention against a (possibly seq-sharded)
  KV cache; no flattening (cache layout wins).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttnSpec
from repro.models.layers import (apply_rope, normal_init, rms_normalize,
                                 yarn_mscale)

Array = jax.Array
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def padded_heads(n_heads: int, head_pad: int) -> int:
    return (n_heads + head_pad - 1) // head_pad * head_pad


def _pad_cols(w, extra: int):
    return jnp.pad(w, ((0, 0), (0, extra))) if extra else w


def init_attn(key, d_model: int, spec: AttnSpec, dtype,
              head_pad: int = 1) -> dict:
    """head_pad > 1 zero-pads the head count to a TP-divisible multiple
    (Megatron-style). Padded v-columns are zero => padded head outputs are
    exactly zero and wo's padded rows never contribute or receive
    gradient — numerically identical to the unpadded model."""
    ks = jax.random.split(key, 8)
    hp = padded_heads(spec.n_heads, head_pad)
    extra = hp - spec.n_heads
    if spec.mla is not None:
        m = spec.mla
        qk_dim = m.qk_nope_dim + m.qk_rope_dim
        if m.q_lora_rank is None:  # one direct query projection
            p = {"wq": _pad_cols(normal_init(
                ks[0], (d_model, spec.n_heads * qk_dim), dtype),
                extra * qk_dim)}
        else:
            p = {"q_a": normal_init(ks[0], (d_model, m.q_lora_rank), dtype),
                 "q_a_norm": {"scale": jnp.ones((m.q_lora_rank,), dtype)},
                 "q_b": _pad_cols(normal_init(
                     ks[1], (m.q_lora_rank, spec.n_heads * qk_dim), dtype),
                     extra * qk_dim)}
        return dict(
            p,
            kv_a=normal_init(ks[2], (d_model,
                                     m.kv_lora_rank + m.qk_rope_dim), dtype),
            kv_a_norm={"scale": jnp.ones((m.kv_lora_rank,), dtype)},
            kv_b=_pad_cols(normal_init(
                ks[3], (m.kv_lora_rank,
                        spec.n_heads * (m.qk_nope_dim + m.v_head_dim)),
                dtype), extra * (m.qk_nope_dim + m.v_head_dim)),
            wo=jnp.pad(normal_init(
                ks[4], (spec.n_heads * m.v_head_dim, d_model), dtype),
                ((0, extra * m.v_head_dim), (0, 0))))
    kv_extra = 0
    if spec.n_kv_heads == spec.n_heads:  # MHA: pad kv in lockstep
        kv_extra = extra
    p = {
        "wq": _pad_cols(normal_init(ks[0], (d_model, spec.q_dim), dtype),
                        extra * spec.head_dim),
        "wk": _pad_cols(normal_init(ks[1], (d_model, spec.kv_dim), dtype),
                        kv_extra * spec.head_dim),
        "wv": _pad_cols(normal_init(ks[2], (d_model, spec.kv_dim), dtype),
                        kv_extra * spec.head_dim),
        "wo": jnp.pad(normal_init(ks[3], (spec.q_dim, d_model), dtype),
                      ((0, extra * spec.head_dim), (0, 0))),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((hp * spec.head_dim,), dtype)
        p["bk"] = jnp.zeros(((spec.n_kv_heads + kv_extra) * spec.head_dim,),
                            dtype)
        p["bv"] = jnp.zeros(((spec.n_kv_heads + kv_extra) * spec.head_dim,),
                            dtype)
    return p


# ---------------------------------------------------------------------------
# Core: causal softmax attention in (B, H, S, D) layout (no dim merging)
# ---------------------------------------------------------------------------


def _expand_kv(k: Array, n_heads: int) -> Array:
    """(B,S,Hk,D) -> (B,S,H,D) by repeating each kv head over its group."""
    b, s, hk, d = k.shape
    if hk == n_heads:
        return k
    return jnp.repeat(k, n_heads // hk, axis=2)


def _mask(q_pos, k_pos, window):
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def _naive_attn(q, k, v, q_pos, k_pos, window, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(q_pos, k_pos, window)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _chunk_body(q, kc, vc, kc_pos, q_pos, window, scale, m, l, acc):
    """Online-softmax step vs one KV chunk. m,l:(B,H,Sq) acc:(B,H,Sq,Dv)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kc,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(q_pos, kc_pos, window)[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def _finalize(m, l, acc, dtype):
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def _chunked_attn(q, k, v, q_pos, k_pos, window, scale, chunk_kv):
    b, h, sq, d = q.shape
    dv = v.shape[-1]
    sk = k.shape[2]
    ck = min(chunk_kv, sk)
    nc = math.ceil(sk / ck)
    pad = nc * ck - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=2**30)
    k_ = k.reshape(b, h, nc, ck, d).transpose(2, 0, 1, 3, 4)
    v_ = v.reshape(b, h, nc, ck, dv).transpose(2, 0, 1, 3, 4)
    kp = k_pos.reshape(nc, ck)

    # Rematerialized: the backward pass recomputes each chunk's scores
    # rather than keeping every chunk's (B, H, Sq, ck) probabilities.
    @partial(jax.checkpoint, prevent_cse=False)
    def body(carry, xs):
        kc, vc, kc_pos = xs
        return _chunk_body(q, kc, vc, kc_pos, q_pos, window, scale,
                           *carry), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (k_, v_, kp))
    return _finalize(m, l, acc, q.dtype)


def _tri_attn(q, k, v, q_pos, k_pos, window, scale, chunk):
    """Triangular (i >= j) pair iteration: causal FLOPs only."""
    b, h, sq, d = q.shape
    dv = v.shape[-1]
    assert sq == k.shape[2], "tri impl is for self-attention train/prefill"
    c = min(chunk, sq)
    nq = math.ceil(sq / c)
    pad = nq * c - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=2**30)
    sq_p = nq * c
    pairs = [(i, j) for i in range(nq) for j in range(i + 1)]
    is_ = jnp.array([p[0] for p in pairs], jnp.int32)
    js_ = jnp.array([p[1] for p in pairs], jnp.int32)

    m0 = jnp.full((b, h, sq_p), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq_p), jnp.float32)
    a0 = jnp.zeros((b, h, sq_p, dv), jnp.float32)

    def body(carry, ij):
        m, l, acc = carry
        i, j = ij
        qi = jax.lax.dynamic_slice_in_dim(q, i * c, c, axis=2)
        qpi = jax.lax.dynamic_slice_in_dim(q_pos, i * c, c)
        kj = jax.lax.dynamic_slice_in_dim(k, j * c, c, axis=2)
        vj = jax.lax.dynamic_slice_in_dim(v, j * c, c, axis=2)
        kpj = jax.lax.dynamic_slice_in_dim(k_pos, j * c, c)
        mi = jax.lax.dynamic_slice_in_dim(m, i * c, c, axis=2)
        li = jax.lax.dynamic_slice_in_dim(l, i * c, c, axis=2)
        ai = jax.lax.dynamic_slice_in_dim(acc, i * c, c, axis=2)
        mi, li, ai = _chunk_body(qi, kj, vj, kpj, qpi, window, scale,
                                 mi, li, ai)
        m = jax.lax.dynamic_update_slice_in_dim(m, mi, i * c, axis=2)
        l = jax.lax.dynamic_update_slice_in_dim(l, li, i * c, axis=2)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, ai, i * c, axis=2)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (is_, js_))
    out = _finalize(m, l, acc, q.dtype)
    return out[:, :, :sq] if pad else out


def _window_attn(q, k, v, q_pos, k_pos, window, scale, chunk_q):
    """Scan over q chunks; slice only the KV span a window can reach."""
    b, h, sq, d = q.shape
    dv = v.shape[-1]
    sk = k.shape[2]
    cq = min(chunk_q, sq)
    nq = math.ceil(sq / cq)
    pad = nq * cq - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
    span = min(sk, window + cq)

    def body(_, xs):
        qi, qpi, i = xs
        start = jnp.clip((i + 1) * cq - span, 0, sk - span)
        kj = jax.lax.dynamic_slice_in_dim(k, start, span, axis=2)
        vj = jax.lax.dynamic_slice_in_dim(v, start, span, axis=2)
        kpj = jax.lax.dynamic_slice_in_dim(k_pos, start, span)
        m0 = jnp.full((b, h, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        a0 = jnp.zeros((b, h, cq, dv), jnp.float32)
        m, l, acc = _chunk_body(qi, kj, vj, kpj, qpi, window, scale,
                                m0, l0, a0)
        return None, _finalize(m, l, acc, q.dtype)

    q_ = q.reshape(b, h, nq, cq, d).transpose(2, 0, 1, 3, 4)
    qp = q_pos.reshape(nq, cq)
    _, outs = jax.lax.scan(body, None, (q_, qp, jnp.arange(nq)))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(b, h, nq * cq, dv)
    return out[:, :, :sq] if pad else out


def attention(q: Array, k: Array, v: Array, *, q_pos: Array, k_pos: Array,
              window: Optional[int] = None, impl: str = "chunked",
              chunk_q: int = 512, chunk_kv: int = 1024,
              scale: Optional[float] = None,
              shard: Optional[Callable] = None) -> Array:
    """Causal MHA. q:(B,Sq,H,D) k,v:(B,Sk,Hk,D[v]). Returns (B,Sq,H,Dv)."""
    b, sq, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    if shard is not None:  # head-TP: batch over dp, (padded) heads over model
        q = shard(q, ("data", None, "model", None))
        k = shard(k, ("data", None, "model", None))
        v = shard(v, ("data", None, "model", None))
    qf = q.transpose(0, 2, 1, 3)  # (B,H,S,D) — transpose, never merge
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    if window is not None and impl != "naive":
        out = _window_attn(qf, kf, vf, q_pos, k_pos, window, scale, chunk_q)
    elif impl == "naive":
        out = _naive_attn(qf, kf, vf, q_pos, k_pos, window, scale)
    elif impl == "tri":
        out = _tri_attn(qf, kf, vf, q_pos, k_pos, window, scale, chunk_q)
    elif impl == "chunked":
        out = _chunked_attn(qf, kf, vf, q_pos, k_pos, window, scale,
                            chunk_kv)
    else:
        raise ValueError(f"unknown attention impl {impl!r}")
    return out.transpose(0, 2, 1, 3)


def decode_attention(q: Array, k_cache: Array, v_cache: Array, *,
                     cur_pos: Array, window: Optional[int] = None,
                     scale: Optional[float] = None) -> Array:
    """Single-step decode. q:(B,1,H,D), caches:(B,S,Hk,D), cur_pos:(B,)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    k_cache = _expand_kv(k_cache, q.shape[2])
    v_cache = _expand_kv(v_cache, q.shape[2])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(k_cache.shape[1])
    mask = kpos[None, :] <= cur_pos[:, None]
    if window is not None:
        mask &= (cur_pos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, v_cache.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full GQA layer (projections + rope + attention [+ cache])
# ---------------------------------------------------------------------------


def _project_qkv(params, x, spec: AttnSpec):
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if spec.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    b, s, _ = x.shape
    q = q.reshape(b, s, -1, spec.head_dim)
    k = k.reshape(b, s, -1, spec.head_dim)
    v = v.reshape(b, s, -1, spec.head_dim)
    return q, k, v


def gqa_forward(params: dict, x: Array, spec: AttnSpec, *, positions: Array,
                impl: str, chunk_q: int, chunk_kv: int,
                cache: Optional[dict] = None,
                shard: Optional[Callable] = None):
    """Train/prefill path. positions: (S,). Returns (out, new_cache|None)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, spec)
    if spec.rope:
        q = apply_rope(q, positions[None, :], spec.rope_theta,
                       spec.rope_scaling)
        k = apply_rope(k, positions[None, :], spec.rope_theta,
                       spec.rope_scaling)
    new_cache = None
    if cache is not None:  # prefill: write into the cache at [0, s)
        new_cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, axis=1),
        }
    out = attention(q, k, v, q_pos=positions, k_pos=positions,
                    window=spec.window, impl=impl, chunk_q=chunk_q,
                    chunk_kv=chunk_kv, shard=shard)
    out = out.reshape(b, s, -1)
    if shard is not None:
        out = shard(out, ("data", None, "model"))
    return out @ params["wo"], new_cache


def gqa_decode(params: dict, x: Array, spec: AttnSpec, *, pos: Array,
               cache: dict):
    """Decode. x:(B,1,d), pos: scalar step index (aligned serving batches).
    Cache update is a dynamic_update_slice (touches one position)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, spec)
    posv = pos[None, None]
    if spec.rope:
        q = apply_rope(q, posv, spec.rope_theta, spec.rope_scaling)
        k = apply_rope(k, posv, spec.rope_theta, spec.rope_scaling)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
    cur = jnp.broadcast_to(pos, (b,))
    out = decode_attention(q, k_cache, v_cache, cur_pos=cur,
                           window=spec.window)
    out = out.reshape(b, s, -1) @ params["wo"]
    return out, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------


def _mla_q(params, x, spec):
    m = spec.mla
    b, s, _ = x.shape
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank is None:
        q = x @ params["wq"]
    else:
        q_c = rms_normalize(x @ params["q_a"]) * params["q_a_norm"]["scale"]
        q = q_c @ params["q_b"]
    q = q.reshape(b, s, -1, qk_dim)
    return jnp.split(q, [m.qk_nope_dim], axis=-1)  # q_nope, q_rope


def _mla_kv_compress(params, x, spec, positions):
    m = spec.mla
    kv = x @ params["kv_a"]
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_normalize(c_kv) * params["kv_a_norm"]["scale"]
    k_rope = apply_rope(k_rope[:, :, None, :], positions, spec.rope_theta,
                        spec.rope_scaling)
    return c_kv, k_rope  # (B,S,r), (B,S,1,rope)


def _mla_scale(spec) -> float:
    """1/sqrt(qk head dim), times YaRN's m(mscale_all_dim)^2."""
    m = spec.mla
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    y = spec.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_expand(params, c_kv, spec):
    m = spec.mla
    b, s, _ = c_kv.shape
    kvb = (c_kv @ params["kv_b"]).reshape(
        b, s, -1, m.qk_nope_dim + m.v_head_dim)
    return jnp.split(kvb, [m.qk_nope_dim], axis=-1)  # k_nope, v


def mla_forward(params: dict, x: Array, spec: AttnSpec, *, positions: Array,
                impl: str, chunk_q: int, chunk_kv: int,
                cache: Optional[dict] = None,
                shard: Optional[Callable] = None):
    m = spec.mla
    b, s, _ = x.shape
    q_nope, q_rope = _mla_q(params, x, spec)
    q_rope = apply_rope(q_rope, positions[None, :], spec.rope_theta,
                        spec.rope_scaling)
    c_kv, k_rope = _mla_kv_compress(params, x, spec, positions[None, :])
    k_nope, v = _mla_expand(params, c_kv, spec)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:3],
                                           m.qk_rope_dim))], axis=-1)
    scale = _mla_scale(spec)
    new_cache = None
    if cache is not None:  # cache the *compressed* kv (the MLA win)
        new_cache = {
            "c_kv": jax.lax.dynamic_update_slice_in_dim(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, axis=1),
            "k_rope": jax.lax.dynamic_update_slice_in_dim(
                cache["k_rope"], k_rope[:, :, 0].astype(
                    cache["k_rope"].dtype), 0, axis=1),
        }
    out = attention(q, k, v, q_pos=positions, k_pos=positions, impl=impl,
                    chunk_q=chunk_q, chunk_kv=chunk_kv, scale=scale,
                    shard=shard)
    out = out.reshape(b, s, -1)
    if shard is not None:
        out = shard(out, ("data", None, "model"))
    return out @ params["wo"], new_cache


def mla_decode(params: dict, x: Array, spec: AttnSpec, *, pos: Array,
               cache: dict, absorb: bool = True):
    """MLA decode against the compressed cache. pos: scalar step index.

    absorb=True uses weight absorption: scores computed directly in the
    kv_lora latent space (no per-token K/V expansion) — the memory-optimal
    decode path. absorb=False expands K/V per step (naive §Perf baseline).
    """
    m = spec.mla
    b, s, _ = x.shape
    cur_pos = jnp.broadcast_to(pos, (b,))
    q_nope, q_rope = _mla_q(params, x, spec)
    q_rope = apply_rope(q_rope, pos[None, None], spec.rope_theta,
                        spec.rope_scaling)
    c_kv_new, k_rope_new = _mla_kv_compress(params, x, spec, pos[None, None])
    c_kv = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv_new.astype(cache["c_kv"].dtype), pos, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope_new[:, :, 0].astype(cache["k_rope"].dtype),
        pos, axis=1)
    new_cache = {"c_kv": c_kv, "k_rope": k_rope}
    scale = _mla_scale(spec)
    kpos = jnp.arange(c_kv.shape[1])
    mask = (kpos[None, :] <= cur_pos[:, None])[:, None, None, :]

    if absorb:
        w_kb = params["kv_b"].reshape(m.kv_lora_rank, -1,
                                      m.qk_nope_dim + m.v_head_dim)
        w_k = w_kb[..., :m.qk_nope_dim]  # (r,H,nope)
        w_v = w_kb[..., m.qk_nope_dim:]  # (r,H,v)
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_k)
        s_lat = jnp.einsum("bqhr,bkr->bhqk", q_lat, c_kv,
                           preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bqhn,bkn->bhqk", q_rope, k_rope,
                            preferred_element_type=jnp.float32)
        sc = (s_lat + s_rope) * scale
        sc = jnp.where(mask, sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        o_lat = jnp.einsum("bhqk,bkr->bqhr", p, c_kv.astype(jnp.float32))
        out = jnp.einsum("bqhr,rhv->bqhv", o_lat.astype(x.dtype), w_v)
    else:
        k_nope, v = _mla_expand(params, c_kv.astype(x.dtype), spec)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :].astype(x.dtype),
                                      (*k_nope.shape[:3], m.qk_rope_dim))],
            axis=-1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(mask, sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bhqk,bkhv->bqhv", p,
                         v.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(b, s, -1) @ params["wo"]
    return out, new_cache
