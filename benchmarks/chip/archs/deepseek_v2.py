"""The program's model for a DeepSeek-V2 configuration file.

``arch_config(cfg)`` returns the program's ArchConfig: the registry's
model named by the file, its published widths (and the router's
published expert count) checked against the file's, cut to the file's
depth and vocabulary, with each MoE layer holding the file's
``n_routed_experts`` experts, [0, n), of the router's published count.
"""
from __future__ import annotations

import dataclasses


def arch_config(cfg: dict):
    from repro.configs import get_config
    from repro.configs.base import Stage
    full = get_config(cfg["registry"])
    dense_stage, moe_stage = full.stages
    (dense,) = dense_stage.block
    (layer,) = moe_stage.block
    a, moe = layer.attn, layer.mlp.moe
    mla, yarn = a.mla, a.rope_scaling
    widths = {
        "hidden_size": full.d_model,
        "intermediate_size": dense.mlp.d_ff,
        "moe_intermediate_size": moe.d_expert,
        "num_attention_heads": a.n_heads,
        "num_key_value_heads": a.n_kv_heads,
        "q_lora_rank": mla.q_lora_rank,
        "kv_lora_rank": mla.kv_lora_rank,
        "qk_nope_head_dim": mla.qk_nope_dim,
        "qk_rope_head_dim": mla.qk_rope_dim,
        "v_head_dim": mla.v_head_dim,
        "attention_bias": a.qkv_bias,
        "n_shared_experts": moe.n_shared,
        "num_experts_per_tok": moe.top_k,
        "norm_topk_prob": moe.norm_topk,
        "first_k_dense_replace": dense_stage.n_layers,
        "hidden_act": {"swiglu": "silu"}.get(layer.mlp.act),
        "rms_norm_eps": full.norm_eps,
        "rope_theta": a.rope_theta,
        "rope_scaling": {
            "beta_fast": yarn.beta_fast, "beta_slow": yarn.beta_slow,
            "factor": yarn.factor, "mscale": yarn.mscale,
            "mscale_all_dim": yarn.mscale_all_dim,
            "original_max_position_embeddings": yarn.original_max_pos,
            "type": "yarn"},
        "tie_word_embeddings": full.tie_embeddings,
        "aux_loss_alpha": full.moe_aux_coef,
    }
    wrong = {k: (v, cfg[k]) for k, v in widths.items() if cfg[k] != v}
    if cfg["published"]["n_routed_experts"] != moe.n_experts:
        wrong["n_routed_experts"] = (moe.n_experts,
                                     cfg["published"]["n_routed_experts"])
    if wrong:
        raise ValueError(f"{cfg['name']}: the program's {cfg['registry']} "
                         f"differs from the file: {wrong}")
    held = dataclasses.replace(layer, mlp=dataclasses.replace(
        layer.mlp, moe=dataclasses.replace(
            moe, held_first=0, held=cfg["n_routed_experts"])))
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return dataclasses.replace(
        full, name=cfg["name"], vocab_size=cfg["vocab_size"],
        stages=(dense_stage, Stage(block=(held,), repeat=n_moe)))
