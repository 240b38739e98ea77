"""Model-layer unit tests: attention impl equivalences, MLA absorb,
mixer decode==forward consistency, MoE dispatch sanity."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import AttnSpec, MLASpec, MambaSpec, MoESpec, RWKVSpec
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S

RNG = np.random.default_rng(0)


def _mk(shape):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32))


def test_attention_impl_equivalence():
    b, s, h, d = 2, 128, 4, 32
    q, k, v = _mk((b, s, h, d)), _mk((b, s, h, d)), _mk((b, s, h, d))
    pos = jnp.arange(s)
    base = A.attention(q, k, v, q_pos=pos, k_pos=pos, impl="naive")
    for impl, kw in [("chunked", dict(chunk_kv=32)),
                     ("tri", dict(chunk_q=32))]:
        out = A.attention(q, k, v, q_pos=pos, k_pos=pos, impl=impl, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-4, rtol=1e-4), impl


def test_window_attention_matches_masked_naive():
    b, s, h, d, w = 1, 128, 2, 16, 24
    q, k, v = _mk((b, s, h, d)), _mk((b, s, h, d)), _mk((b, s, h, d))
    pos = jnp.arange(s)
    out = A.attention(q, k, v, q_pos=pos, k_pos=pos, window=w, chunk_q=32)
    exp = A.attention(q, k, v, q_pos=pos, k_pos=pos, window=w, impl="naive")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4,
                               rtol=1e-4)


def test_gqa_prefill_decode_consistency():
    """Prefill then decode the next token == forward over S+1 tokens."""
    spec = AttnSpec(n_heads=4, n_kv_heads=2, head_dim=16)
    d_model = 32
    params = A.init_attn(jax.random.key(0), d_model, spec, jnp.float32)
    s = 24
    x = _mk((2, s + 1, d_model))
    full, _ = A.gqa_forward(params, x, spec, positions=jnp.arange(s + 1),
                            impl="naive", chunk_q=16, chunk_kv=16)
    cache = {"k": jnp.zeros((2, s + 8, 2, 16)),
             "v": jnp.zeros((2, s + 8, 2, 16))}
    _, cache = A.gqa_forward(params, x[:, :s], spec,
                             positions=jnp.arange(s), impl="naive",
                             chunk_q=16, chunk_kv=16, cache=cache)
    step, _ = A.gqa_decode(params, x[:, s:s + 1], spec,
                           pos=jnp.asarray(s, jnp.int32), cache=cache)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, s]), atol=1e-4, rtol=1e-4)


def test_mla_absorb_equals_expand():
    spec = AttnSpec(n_heads=4, n_kv_heads=4, head_dim=16,
                    mla=MLASpec(q_lora_rank=24, kv_lora_rank=16,
                                qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8))
    d_model = 32
    params = A.init_attn(jax.random.key(1), d_model, spec, jnp.float32)
    s = 16
    x = _mk((2, s, d_model))
    cache = {"c_kv": jnp.zeros((2, s + 4, 16)),
             "k_rope": jnp.zeros((2, s + 4, 8))}
    _, cache = A.mla_forward(params, x, spec, positions=jnp.arange(s),
                             impl="naive", chunk_q=8, chunk_kv=8,
                             cache=cache)
    xt = _mk((2, 1, d_model))
    o1, _ = A.mla_decode(params, xt, spec, pos=jnp.asarray(s, jnp.int32),
                         cache=cache, absorb=True)
    o2, _ = A.mla_decode(params, xt, spec, pos=jnp.asarray(s, jnp.int32),
                         cache=cache, absorb=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4,
                               rtol=1e-4)


def test_head_padding_exactness():
    spec = AttnSpec(n_heads=3, n_kv_heads=3, head_dim=8)  # 3 % 4 != 0
    d_model = 24
    key = jax.random.key(2)
    p1 = A.init_attn(key, d_model, spec, jnp.float32, head_pad=1)
    p4 = A.init_attn(key, d_model, spec, jnp.float32, head_pad=4)
    x = _mk((2, 16, d_model))
    o1, _ = A.gqa_forward(p1, x, spec, positions=jnp.arange(16),
                          impl="naive", chunk_q=8, chunk_kv=8)
    o4, _ = A.gqa_forward(p4, x, spec, positions=jnp.arange(16),
                          impl="naive", chunk_q=8, chunk_kv=8)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o4), atol=1e-5,
                               rtol=1e-5)


def test_mamba_decode_matches_forward():
    spec = MambaSpec(d_state=4, d_conv=4, expand=2, dt_rank=4)
    d_model = 16
    params = S.init_mamba_full(jax.random.key(3), d_model, spec,
                               jnp.float32)
    s = 32
    x = _mk((2, s + 1, d_model)) * 0.3
    full, _ = S.mamba_forward(params, x, spec, d_model, chunk=8)
    cache = {"conv": jnp.zeros((2, 3, 32)), "ssm": jnp.zeros((2, 32, 4))}
    _, cache = S.mamba_forward(params, x[:, :s], spec, d_model, chunk=8,
                               cache=cache)
    step, _ = S.mamba_decode(params, x[:, s:s + 1], spec, d_model,
                             cache=cache)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, s]), atol=1e-3, rtol=1e-3)


def test_rwkv_decode_matches_forward():
    spec = RWKVSpec(head_dim=8, decay_lora=8, mix_lora=4, d_ffn=32)
    d_model = 16
    params = S.init_rwkv(jax.random.key(4), d_model, spec, jnp.float32)
    s = 16
    x = _mk((2, s + 1, d_model)) * 0.3
    full, _ = S.rwkv_time_mix(params, x, spec, chunk=4, mode="train")
    cache = {"shift_tm": jnp.zeros((2, d_model)),
             "wkv": jnp.zeros((2, 2, 8, 8)),
             "shift_cm": jnp.zeros((2, d_model))}
    _, c2 = S.rwkv_time_mix(params, x[:, :s], spec, chunk=4, cache=cache,
                            mode="prefill")
    c2["shift_cm"] = cache["shift_cm"]
    step, _ = S.rwkv_time_mix(params, x[:, s:s + 1], spec, cache=c2,
                              mode="decode")
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, s]), atol=1e-3, rtol=1e-3)


# logw ranges: no decay (the upper clip), a typical decay, near the lower clip
LOGW_RANGES = {"flat": (-2e-5, -1e-5), "mid": (-0.9, -0.1),
               "steep": (-20.0, -15.0)}
RWKV_CASES = [(l, w) for l in (4, 16, 40, 64) for w in sorted(LOGW_RANGES)]


def _wkv_tokens(r, k, v, logw, u, state0):
    """The wkv recurrence one token at a time. r/k/v/logw:(B,T,H,d),
    state0:(B,H,dk,dv). Returns y:(B,T,H,dv) and the final state."""
    def step(st, xs):
        rt, kt, vt, wt = xs  # (B,H,d)
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhd,bhdv->bhv", rt, st + u[..., None] * kv,
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.exp(wt)[..., None] * st + kv, y

    state, ys = jax.lax.scan(step, state0, tuple(
        t.swapaxes(0, 1) for t in (r, k, v, logw)))
    return ys.swapaxes(0, 1), state


def _rel_err(got, want, floor=0.0):
    """Largest error over the largest reference value (or ``floor``)."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want))
                 / max(np.max(np.abs(want)), floor))


def _grads_close(grads, grads_ref, names):
    """Each gradient within 1e-4 of its largest value. A gradient that the
    steepest decays drive down to f32 rounding noise (logw's and w0's:
    terms of order 1 cancel in it) is held to 1e-6 of the largest
    gradient of all instead."""
    floor = 1e-2 * max(float(np.max(np.abs(np.asarray(g))))
                       for g in grads_ref)
    for name, g, g_ref in zip(names, grads, grads_ref):
        assert np.isfinite(np.asarray(g)).all(), name
        assert _rel_err(g, g_ref, floor) < 1e-4, name


@pytest.mark.parametrize("length,logw_range", RWKV_CASES)
def test_rwkv_chunk_matches_token_recurrence(length, logw_range):
    b, h, d = 2, 2, 8
    lo, hi = LOGW_RANGES[logw_range]
    rng = np.random.default_rng(length)
    r, k, v = (jnp.asarray(rng.standard_normal((b, h, length, d)),
                           jnp.float32) for _ in range(3))
    logw = jnp.asarray(rng.uniform(lo, hi, (b, h, length, d)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, d)), jnp.float32)
    state0 = jnp.asarray(rng.standard_normal((b, h, d, d)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((b, h, length, d)), jnp.float32)
    cs = jnp.asarray(rng.standard_normal((b, h, d, d)), jnp.float32)

    def tokens(r, k, v, logw, u, state0):  # (B,H,L,d) like the chunk
        y, st = _wkv_tokens(*(t.swapaxes(1, 2) for t in (r, k, v, logw)),
                            u, state0)
        return y.swapaxes(1, 2), st

    args = (r, k, v, logw, u, state0)
    y, st = jax.jit(S._rwkv_chunk)(*args)
    y_ref, st_ref = jax.jit(tokens)(*args)
    assert _rel_err(y, y_ref) < 2e-5
    assert _rel_err(st, st_ref) < 2e-5

    def loss(fn):
        def f(*a):
            y, st = fn(*a)
            return jnp.sum(y * ct) + jnp.sum(st * cs)
        return jax.jit(jax.grad(f, argnums=tuple(range(6))))

    _grads_close(loss(S._rwkv_chunk)(*args), loss(tokens)(*args),
                 ("r", "k", "v", "logw", "u", "state0"))


@pytest.mark.parametrize("length,logw_range", RWKV_CASES)
def test_rwkv_time_mix_matches_token_recurrence(length, logw_range,
                                                 monkeypatch):
    """The chunked scan inside rwkv_time_mix (chunk = length, a sequence of
    two chunks and a padded third) against the same layer with the
    token-by-token recurrence in its place, from a non-zero state."""
    spec = RWKVSpec(head_dim=8, decay_lora=8, mix_lora=4, d_ffn=32)
    d_model, b, seq = 16, 2, 2 * length + 3
    lo, hi = LOGW_RANGES[logw_range]
    params = S.init_rwkv(jax.random.key(length), d_model, spec, jnp.float32)
    # logw = -exp(w0 + lora), the lora term small: centre it in the range
    params["w0"] = jnp.full((d_model,), np.log(-(lo + hi) / 2), jnp.float32)
    params["dw2"] = params["dw2"] * 0.1
    rng = np.random.default_rng(length + 1)
    x = jnp.asarray(rng.standard_normal((b, seq, d_model)) * 0.3,
                    jnp.float32)
    cache = {"shift_tm": jnp.zeros((b, d_model), jnp.float32),
             "wkv": jnp.asarray(rng.standard_normal((b, 2, 8, 8)),
                                jnp.float32)}

    def run(p, x, state0):
        out, new = S.rwkv_time_mix(p, x, spec, chunk=length,
                                   cache=dict(cache, wkv=state0),
                                   mode="prefill")
        return out, new["wkv"]

    def loss(p, x, state0):
        out, st = run(p, x, state0)
        return jnp.sum(out ** 2) + jnp.sum(st ** 2)

    def results():  # traced anew: reads S._wkv as it stands
        return (jax.jit(run)(params, x, cache["wkv"]),
                jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
                    params, x, cache["wkv"]))

    got, grads = results()
    monkeypatch.setattr(S, "_wkv", lambda r, k, v, logw, u, state0, chunk:
                        _wkv_tokens(r, k, v, logw, u, state0))
    want, grads_ref = results()
    assert _rel_err(got[0], want[0]) < 2e-5
    assert _rel_err(got[1], want[1]) < 2e-5
    paths, leaves = zip(*jax.tree_util.tree_leaves_with_path(grads))
    _grads_close(leaves, jax.tree_util.tree_leaves(grads_ref),
                 [jax.tree_util.keystr(p) for p in paths])


def test_moe_routing_sanity():
    spec = MoESpec(n_experts=4, top_k=2, d_expert=16, n_shared=1)
    d_model = 8
    params = M.init_moe(jax.random.key(5), d_model, spec, "swiglu",
                        jnp.float32)
    x = _mk((2, 16, d_model))
    y, aux = M.apply_moe(params, x, spec, "swiglu", n_groups=2,
                         capacity_factor=2.0)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert 0.5 < float(aux) < 4.0  # balanced-ish routing near init

    # generous capacity: moe output must not depend on group split
    y1, _ = M.apply_moe(params, x, spec, "swiglu", n_groups=1,
                        capacity_factor=4.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1), atol=1e-4,
                               rtol=1e-4)


def test_moe_capacity_drops_overflow():
    spec = MoESpec(n_experts=2, top_k=1, d_expert=8)
    d_model = 4
    params = M.init_moe(jax.random.key(6), d_model, spec, "swiglu",
                        jnp.float32)
    # tiny capacity factor forces drops; output must stay finite
    x = _mk((1, 32, d_model))
    y, _ = M.apply_moe(params, x, spec, "swiglu", n_groups=1,
                       capacity_factor=0.1)
    assert np.isfinite(np.asarray(y)).all()
