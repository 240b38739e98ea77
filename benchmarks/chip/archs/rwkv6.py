"""The program's model for an rwkv6 configuration file.

``arch_config(cfg)`` returns the program's ArchConfig: the registry's
model named by the file, its published widths checked against the
file's, cut to the file's depth and vocabulary.
"""
from __future__ import annotations

import dataclasses


def arch_config(cfg: dict):
    from repro.configs import get_config
    from repro.configs.base import Stage
    full = get_config(cfg["registry"])
    (stage,) = full.stages
    (layer,) = stage.block
    widths = {"hidden_size": full.d_model,
              "head_size": layer.rwkv.head_dim,
              "intermediate_size": layer.rwkv.d_ffn,
              "time_mix_extra_dim": layer.rwkv.mix_lora,
              "time_decay_extra_dim": layer.rwkv.decay_lora,
              "layer_norm_epsilon": full.norm_eps,
              "tie_word_embeddings": full.tie_embeddings}
    wrong = {k: (v, cfg[k]) for k, v in widths.items() if cfg[k] != v}
    if wrong:
        raise ValueError(f"{cfg['name']}: the program's {cfg['registry']} "
                         f"differs from the file: {wrong}")
    return dataclasses.replace(
        full, name=cfg["name"], vocab_size=cfg["vocab_size"],
        stages=(Stage(block=stage.block,
                      repeat=cfg["num_hidden_layers"]),))
