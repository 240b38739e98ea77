"""Per-assigned-architecture smoke tests: reduced config, one forward /
train step on CPU, asserting output shapes + finite values; plus a
prefill+decode step. The FULL configs are exercised only via the dry-run
(ShapeDtypeStruct; launch/dryrun.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, reduced
from repro.models.transformer import Model, init_params, count_params


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_train_step(arch, small_rc):
    cfg = reduced(get_config(arch))
    m = Model(cfg, small_rc)
    params = m.init(jax.random.key(0))
    b, s = 2, 64
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    if cfg.n_frontend:
        batch["frontend_embeds"] = jnp.zeros((b, cfg.n_frontend,
                                              cfg.d_model))

    def loss_of(p):
        return m.loss(p, batch)[0]

    loss, grads = jax.value_and_grad(loss_of)(params)
    assert np.isfinite(float(loss)), arch
    gn = sum(float(jnp.sum(jnp.square(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, arch


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "gemma3-1b",
                                  "rwkv6-1.6b", "deepseek-moe-16b",
                                  "minicpm3-4b"])
def test_reduced_prefill_decode(arch, small_rc):
    cfg = reduced(get_config(arch))
    m = Model(cfg, small_rc)
    params = m.init(jax.random.key(0))
    b, s = 2, 32
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0,
                                cfg.vocab_size)
    fe = jnp.zeros((b, cfg.n_frontend, cfg.d_model)) if cfg.n_frontend \
        else None
    caches = m.init_cache(b, s + cfg.n_frontend + 4)
    logits, caches = m.prefill(params, tokens, caches, fe)
    assert np.isfinite(np.asarray(logits)).all(), arch
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos = jnp.asarray(s + cfg.n_frontend, jnp.int32)
    logits2, caches = m.decode_step(params, tok, pos, caches)
    assert logits2.shape[0] == b
    assert np.isfinite(np.asarray(logits2)).all(), arch


def test_param_counts_match_published():
    expect = {  # billions, tolerance 5%
        "jamba-1.5-large-398b": 398.0, "qwen1.5-32b": 35.2,
        "stablelm-12b": 12.1, "minicpm3-4b": 4.1, "gemma3-1b": 1.0,
        "phi3.5-moe-42b-a6.6b": 41.9, "deepseek-moe-16b": 16.4,
        "rwkv6-1.6b": 1.6, "qwen2-vl-2b": 1.5, "musicgen-large": 2.4,
        "deepseek-v2-lite": 15.7,
        "rwkv6-1.6b-2l-v16k": 0.178,  # configs/rwkv6_1_6b_2l_v16k.py
    }
    for arch, bn in expect.items():
        n = count_params(get_config(arch)) / 1e9
        assert abs(n - bn) / bn < 0.05, (arch, n, bn)


def test_long_500k_applicability_flags():
    from repro.configs import SHAPES, shape_applicable
    ls = SHAPES["long_500k"]
    runs = {a for a in ARCH_IDS
            if shape_applicable(get_config(a), ls)}
    assert runs == {"jamba-1.5-large-398b", "rwkv6-1.6b",
                    "rwkv6-1.6b-2l-v16k", "gemma3-1b"}


def test_train_step_counts_expert_rows(small_rc):
    """The train step's metrics carry the MoE counters: every (token,
    expert) assignment of every MoE layer computed (all experts held in
    the reduced model, none dropped), and the most rows of one expert."""
    from repro.launch.train import make_train_step
    from repro.optim.adamw import AdamWConfig, adamw_init
    cfg = reduced(get_config("deepseek-v2-lite"))
    moe = [s.mlp.moe for s in cfg.layer_specs() if s.mlp.kind == "moe"]
    params = init_params(cfg, jax.random.key(0), small_rc)
    b, s = 2, 64
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    step = make_train_step(cfg, small_rc, AdamWConfig())
    _, _, metrics = step(params, adamw_init(params), batch)
    assert int(metrics["moe_rows"]) == b * s * sum(m.top_k for m in moe)
    top_k, n_experts = moe[0].top_k, moe[0].n_experts
    assert b * s * top_k / n_experts <= int(metrics["moe_max_rows"]) <= b * s
    assert float(metrics["aux"]) > 0
