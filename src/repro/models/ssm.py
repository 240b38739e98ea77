"""State-space mixers: Mamba (Jamba's) and RWKV6 "Finch" time/channel mix.

Both use *chunked* scans for train/prefill: a sequential outer scan over
sequence chunks carrying O(1) recurrent state, with parallel intra-chunk
work — the TPU-native adaptation of the CUDA selective-scan kernels (see
DESIGN.md), in plain jnp. Decode is a single-step state update.

Numerics: decays and states are f32; all pairwise decay terms are
exp(negative) — no overflow by construction.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MambaSpec, RWKVSpec
from repro.models.layers import normal_init

Array = jax.Array


# ===========================================================================
# Mamba
# ===========================================================================


def init_mamba(key, d_model: int, spec: MambaSpec, dtype) -> dict:
    di = spec.d_inner(d_model)
    r = spec.resolved_dt_rank(d_model)
    ks = jax.random.split(key, 6)
    return {
        "in_proj": normal_init(ks[0], (d_model, 2 * di), dtype),
        "conv_w": normal_init(ks[1], (spec.d_conv, di), dtype, std=0.1),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": normal_init(ks[2], (di, r + 2 * spec.d_state), dtype),
        "dt_proj": normal_init(ks[3], (r, di), dtype, std=r ** -0.5),
        "dt_bias": jnp.full((di,), -4.6, dtype),  # softplus^-1(0.01)
        "A_log": jnp.log(jnp.arange(1, spec.d_state + 1,
                                    dtype=jnp.float32))[None, :]
        * jnp.ones((di, 1), jnp.float32),
        "D": jnp.ones((di,), jnp.float32),
    }


def _causal_depthwise_conv(x: Array, w: Array, b: Array) -> Array:
    """x:(B,S,di), w:(K,di) causal depthwise conv."""
    k = w.shape[0]
    di = x.shape[-1]
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        w[:, None, :].astype(jnp.float32),  # (K, 1, di): (spatial, in/g, out)
        window_strides=(1,),
        padding=[(k - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=di,
    )
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _mamba_ssm_params(params, xc, spec: MambaSpec, d_model: int):
    """xc:(B,S,di) post-conv. Returns decay_log, u, C — all f32."""
    r = spec.resolved_dt_rank(d_model)
    dbc = xc @ params["x_proj"]
    dt, bmat, cmat = jnp.split(dbc, [r, r + spec.d_state], axis=-1)
    dt = jax.nn.softplus(
        (dt @ params["dt_proj"]).astype(jnp.float32)
        + params["dt_bias"].astype(jnp.float32))  # (B,S,di)
    a = -jnp.exp(params["A_log"])  # (di, ds)
    decay_log = dt[..., None] * a  # (B,S,di,ds) <= 0
    u = (dt * xc.astype(jnp.float32))[..., None] * \
        bmat.astype(jnp.float32)[:, :, None, :]
    return decay_log, u, cmat.astype(jnp.float32)


def _chunk_scan(decay_log, u, c, state0):
    """One chunk: decay_log,u:(B,L,di,ds), c:(B,L,ds), state0:(B,di,ds)."""
    a = jnp.exp(decay_log)

    def op(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, b1 * a2 + b2

    a_cum, s_intra = jax.lax.associative_scan(op, (a, u), axis=1)
    s = s_intra + a_cum * state0[:, None]
    y = jnp.einsum("blds,bls->bld", s, c)
    return y, s[:, -1]


def mamba_forward(params: dict, x: Array, spec: MambaSpec, d_model: int, *,
                  chunk: int = 128, cache: Optional[dict] = None):
    """Train/prefill. x:(B,S,d). Returns (out, new_cache|None)."""
    b, s, _ = x.shape
    di = spec.d_inner(d_model)
    xz = x @ params["in_proj"]
    xu, z = jnp.split(xz, 2, axis=-1)
    xc = jax.nn.silu(
        _causal_depthwise_conv(xu, params["conv_w"], params["conv_b"])
        .astype(jnp.float32)).astype(x.dtype)
    decay_log, u, cmat = _mamba_ssm_params(params, xc, spec, d_model)

    l = min(chunk, s)
    pad = (-s) % l
    if pad:  # identity padding: decay=exp(0)=1, u=0 -> state unchanged
        decay_log = jnp.pad(decay_log, ((0, 0), (0, pad), (0, 0), (0, 0)))
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nc = sp // l
    dl_ = decay_log.reshape(b, nc, l, di, spec.d_state).transpose(1, 0, 2, 3, 4)
    u_ = u.reshape(b, nc, l, di, spec.d_state).transpose(1, 0, 2, 3, 4)
    c_ = cmat.reshape(b, nc, l, spec.d_state).transpose(1, 0, 2, 3)

    state0 = jnp.zeros((b, di, spec.d_state), jnp.float32)

    def body(st, xs):
        dl_c, u_c, c_c = xs
        y, st_new = _chunk_scan(dl_c, u_c, c_c, st)
        return st_new, y

    state, ys = jax.lax.scan(body, state0, (dl_, u_, c_))
    y = ys.transpose(1, 0, 2, 3).reshape(b, sp, di)[:, :s]
    y = y + params["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = y @ params["out_proj"]
    new_cache = None
    if cache is not None:
        k = spec.d_conv - 1
        new_cache = {"conv": xu[:, -k:].astype(cache["conv"].dtype),
                     "ssm": state}
    return out, new_cache


def mamba_decode(params: dict, x: Array, spec: MambaSpec, d_model: int, *,
                 cache: dict):
    """x:(B,1,d). cache: conv (B,K-1,di), ssm (B,di,ds)."""
    b, _, _ = x.shape
    xz = x @ params["in_proj"]
    xu, z = jnp.split(xz, 2, axis=-1)  # (B,1,di)
    window = jnp.concatenate([cache["conv"].astype(xu.dtype), xu], axis=1)
    conv = jnp.einsum("bkd,kd->bd", window.astype(jnp.float32),
                      params["conv_w"].astype(jnp.float32))
    xc = jax.nn.silu(conv + params["conv_b"].astype(jnp.float32))[:, None]
    xc = xc.astype(x.dtype)
    decay_log, u, cmat = _mamba_ssm_params(params, xc, spec, d_model)
    state = jnp.exp(decay_log[:, 0]) * cache["ssm"] + u[:, 0]
    y = jnp.einsum("bds,bs->bd", state, cmat[:, 0])[:, None]
    y = y + params["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = y @ params["out_proj"]
    return out, {"conv": window[:, 1:].astype(cache["conv"].dtype),
                 "ssm": state}


def init_mamba_full(key, d_model: int, spec: MambaSpec, dtype) -> dict:
    p = init_mamba(key, d_model, spec, dtype)
    di = spec.d_inner(d_model)
    p["out_proj"] = normal_init(jax.random.fold_in(key, 7), (di, d_model),
                                dtype)
    return p


# ===========================================================================
# RWKV6 (Finch)
# ===========================================================================

_MIX_NAMES = ("r", "k", "v", "g", "w")


def init_rwkv(key, d_model: int, spec: RWKVSpec, dtype) -> dict:
    h = d_model // spec.head_dim
    ks = jax.random.split(key, 16)
    p = {
        "mix_mu": normal_init(ks[0], (5, d_model), dtype, std=0.1),
        "mix_x": normal_init(ks[1], (d_model,), dtype, std=0.1),
        "mix_w1": normal_init(ks[2], (d_model, 5 * spec.mix_lora), dtype),
        "mix_w2": normal_init(ks[3], (5, spec.mix_lora, d_model), dtype),
        "wr": normal_init(ks[4], (d_model, d_model), dtype),
        "wk": normal_init(ks[5], (d_model, d_model), dtype),
        "wv": normal_init(ks[6], (d_model, d_model), dtype),
        "wg": normal_init(ks[7], (d_model, d_model), dtype),
        "wo": normal_init(ks[8], (d_model, d_model), dtype),
        "w0": jnp.full((d_model,), -1.0, jnp.float32),
        "dw1": normal_init(ks[9], (d_model, spec.decay_lora), dtype),
        "dw2": normal_init(ks[10], (spec.decay_lora, d_model), dtype),
        "bonus_u": normal_init(ks[11], (h, spec.head_dim), jnp.float32,
                               std=0.5),
        "ln_x_scale": jnp.ones((d_model,), dtype),
        "ln_x_bias": jnp.zeros((d_model,), dtype),
    }
    return p


def init_rwkv_channel(key, d_model: int, spec: RWKVSpec, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "cmu_k": normal_init(jax.random.fold_in(key, 9), (d_model,), dtype,
                             std=0.1),
        "cmu_r": normal_init(jax.random.fold_in(key, 10), (d_model,), dtype,
                             std=0.1),
        "ck": normal_init(ks[0], (d_model, spec.d_ffn), dtype),
        "cv": normal_init(ks[1], (spec.d_ffn, d_model), dtype),
        "cr": normal_init(ks[2], (d_model, d_model), dtype),
    }


def _token_shift(x: Array, prev: Optional[Array]) -> Array:
    """Shift right by one along S; position 0 sees `prev` (or zeros)."""
    b, s, d = x.shape
    first = jnp.zeros((b, 1, d), x.dtype) if prev is None else \
        prev[:, None].astype(x.dtype)
    return jnp.concatenate([first, x[:, :-1]], axis=1) if s > 1 else first


def _ddlerp(params, x, xx):
    """Data-dependent lerp producing the 5 mixed inputs (r,k,v,g,w)."""
    dx = (xx - x).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    base = xf + dx * params["mix_x"].astype(jnp.float32)
    lora = jnp.tanh(base.astype(x.dtype) @ params["mix_w1"])  # (B,S,5*ml)
    b, s, _ = x.shape
    lora = lora.reshape(b, s, 5, -1)
    dyn = jnp.einsum("bsfm,fmd->bsfd", lora, params["mix_w2"])  # (B,S,5,d)
    mix = params["mix_mu"].astype(jnp.float32) + dyn.astype(jnp.float32)
    out = xf[:, :, None, :] + dx[:, :, None, :] * mix
    return [out[:, :, i].astype(x.dtype) for i in range(5)]


def _rwkv_proj(params, xs, h, dh):
    xr, xk, xv, xg, xw = xs
    b, s, _ = xr.shape
    r = (xr @ params["wr"]).reshape(b, s, h, dh)
    k = (xk @ params["wk"]).reshape(b, s, h, dh)
    v = (xv @ params["wv"]).reshape(b, s, h, dh)
    g = jax.nn.silu((xg @ params["wg"]).astype(jnp.float32))
    logw = -jnp.exp(
        params["w0"].astype(jnp.float32)
        + (jnp.tanh(xw @ params["dw1"]) @ params["dw2"]).astype(jnp.float32))
    logw = jnp.clip(logw, -20.0, -1e-5).reshape(b, s, h, dh)
    return r, k, v, g, logw


def _rwkv_chunk(r, k, v, logw, u, state0):
    """One wkv chunk. r/k/v/logw:(B,L,H,dk|dv), state0:(B,H,dk,dv) f32."""
    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lwc = jnp.cumsum(logw, axis=1)  # inclusive
    ex = lwc - logw  # exclusive
    # inter-chunk: r_t . (exp(ex_t) * S0)
    y_inter = jnp.einsum("blhd,bhdv->blhv", rf * jnp.exp(ex), state0)
    # intra-chunk pairwise decays (strictly s < t): exp(ex_t - lwc_s) <= 1
    diff = ex[:, :, None] - lwc[:, None, :]  # (B,Lt,Ls,H,dk)
    tri = jnp.tril(jnp.ones((r.shape[1], r.shape[1]), jnp.float32), k=-1)
    pair = jnp.exp(jnp.minimum(diff, 0.0)) * tri[None, :, :, None, None]
    amat = jnp.einsum("bthd,bshd,btshd->bhts", rf, kf, pair)
    diag = jnp.einsum("bthd,hd,bthd->bth", rf, u, kf)  # bonus on s=t
    y_intra = jnp.einsum("bhts,bshv->bthv", amat, vf) \
        + diag[..., None].transpose(0, 1, 2, 3) * vf
    # new state: exp(lwc_L)*S0 + sum_s exp(lwc_L - lwc_s) k_s (x) v_s
    w_all = jnp.exp(lwc[:, -1])  # (B,H,dk)
    k_dec = kf * jnp.exp(lwc[:, -1][:, None] - lwc)
    s_new = w_all[..., None] * state0 + jnp.einsum("bshd,bshv->bhdv", k_dec,
                                                   vf)
    return y_inter + y_intra, s_new


def rwkv_time_mix(params: dict, x: Array, spec: RWKVSpec, *, chunk: int = 64,
                  cache: Optional[dict] = None, mode: str = "train"):
    """Returns (out, new_cache|None). cache keys: shift_tm (B,d),
    wkv (B,H,dk,dv) f32."""
    b, s, d = x.shape
    h, dh = d // spec.head_dim, spec.head_dim
    prev = cache["shift_tm"] if cache is not None else None
    if mode == "decode":
        xx = prev[:, None].astype(x.dtype)
    else:
        xx = _token_shift(x, prev if mode == "decode" else None)
    xs = _ddlerp(params, x, xx)
    r, k, v, g, logw = _rwkv_proj(params, xs, h, dh)
    u = params["bonus_u"]

    if mode == "decode":
        state0 = cache["wkv"]
        kf = k.astype(jnp.float32)[:, 0]
        vf = v.astype(jnp.float32)[:, 0]
        rf = r.astype(jnp.float32)[:, 0]
        kv = kf[..., None] * vf[..., None, :]  # (B,H,dk,dv)
        y = jnp.einsum("bhd,bhdv->bhv", rf, state0 + u[..., None] * kv)
        state = jnp.exp(logw[:, 0])[..., None] * state0 + kv
        y = y[:, None]  # (B,1,H,dv)
        new_cache = {"shift_tm": x[:, -1], "wkv": state}
    else:
        l = min(chunk, s)
        pad = (-s) % l
        if pad:
            zp = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                                   (t.ndim - 2))
            r, k, v, logw = zp(r), zp(k), zp(v), zp(logw)
        sp = s + pad
        nc = sp // l

        def split(t):
            return t.reshape(b, nc, l, *t.shape[2:]).transpose(
                1, 0, 2, *range(3, t.ndim + 1))

        state0 = cache["wkv"] if cache is not None else \
            jnp.zeros((b, h, dh, dh), jnp.float32)

        # rematerialized per chunk: the backward pass keeps only each
        # chunk's inputs and carried state, not its (B,L,L,H,dk) pairwise
        # decays — at published widths those would take tens of GB
        @partial(jax.checkpoint, prevent_cse=False)
        def body(st, xs_):
            rc, kc, vc, lwc = xs_
            y, st_new = _rwkv_chunk(rc, kc, vc, lwc, u, st)
            return st_new, y

        state, ys = jax.lax.scan(body, state0,
                                 (split(r), split(k), split(v), split(logw)))
        y = ys.transpose(1, 0, 2, 3, 4).reshape(b, sp, h, dh)[:, :s]
        new_cache = {"shift_tm": x[:, -1], "wkv": state} \
            if cache is not None else None

    # Per-head groupnorm, then gate and output-project.
    yf = y.reshape(b, -1, h, dh)
    mu = yf.mean(-1, keepdims=True)
    var = jnp.var(yf, axis=-1, keepdims=True)
    yn = (yf - mu) * jax.lax.rsqrt(var + 1e-5)
    yn = yn.reshape(b, -1, d) * params["ln_x_scale"].astype(jnp.float32) \
        + params["ln_x_bias"].astype(jnp.float32)
    out = (yn * g).astype(x.dtype) @ params["wo"]
    return out, new_cache


def rwkv_channel_mix(params: dict, x: Array, *,
                     cache: Optional[dict] = None, mode: str = "train"):
    """RWKV6 channel mix. cache key: shift_cm (B,d)."""
    prev = cache["shift_cm"] if cache is not None else None
    if mode == "decode":
        xx = prev[:, None].astype(x.dtype)
    else:
        xx = _token_shift(x, None)
    dx = xx - x
    xk = x + dx * params["cmu_k"]
    xr = x + dx * params["cmu_r"]
    kk = jnp.square(jax.nn.relu((xk @ params["ck"]).astype(jnp.float32)))
    vv = kk.astype(x.dtype) @ params["cv"]
    rr = jax.nn.sigmoid((xr @ params["cr"]).astype(jnp.float32))
    out = (rr * vv.astype(jnp.float32)).astype(x.dtype)
    new_cache = {"shift_cm": x[:, -1]} if cache is not None else None
    return out, new_cache
