"""Shared layer primitives: norms, activations, positional encodings, init."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def normal_init(key, shape, dtype, std=0.02):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms (computed in f32, cast back)
# ---------------------------------------------------------------------------


def init_norm(kind: str, dim: int, dtype) -> dict:
    p = {"scale": jnp.ones((dim,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def apply_norm(kind: str, params: dict, x: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(kind)
    y = y * params["scale"].astype(jnp.float32)
    if "bias" in params:
        y = y + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_normalize(x: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def act_fn(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(dim: int, theta: float, scaling=None) -> Array:
    """Inverse frequencies; ``scaling`` (a YarnRope) blends base/factor
    into the low frequencies over YaRN's correction range."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is None:
        return freqs

    def corr(rotations):  # the dim whose wavelength fits ``rotations``
        return dim * math.log(scaling.original_max_pos
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(scaling.beta_fast)), 0)
    high = min(math.ceil(corr(scaling.beta_slow)), dim - 1)
    high = high if high > low else low + 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: Array, positions: Array, theta: float,
               scaling=None) -> Array:
    """x: (..., S, H, D) with rotary over D (halves rotated as pairs);
    positions: (..., S). ``scaling``: a YarnRope, or None."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, d/2)
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(positions: Array, dim: int) -> Array:
    """positions: (..., S) -> (..., S, dim)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Dense / gated MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, act: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {
            "w_gate": normal_init(ks[0], (d_model, d_ff), dtype),
            "w_up": normal_init(ks[1], (d_model, d_ff), dtype),
            "w_down": normal_init(ks[2], (d_ff, d_model), dtype),
        }
    return {  # plain (non-gated) MLP, e.g. MusicGen
        "w_up": normal_init(ks[1], (d_model, d_ff), dtype),
        "w_down": normal_init(ks[2], (d_ff, d_model), dtype),
    }


def apply_mlp(params: dict, x: Array, act: str) -> Array:
    up = x @ params["w_up"]
    if "w_gate" in params:
        gate = x @ params["w_gate"]
        inner = {"swiglu": jax.nn.silu, "geglu": jax.nn.gelu}[act]
        h = inner(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    return h @ params["w_down"]


def softcap(logits: Array, cap: float) -> Array:
    if cap <= 0:
        return logits
    lf = logits.astype(jnp.float32)
    return (jnp.tanh(lf / cap) * cap).astype(logits.dtype)
