"""Operations one DeepSeek-V2 training step requires, from the
configuration's sizes: the forward and backward passes' matrix products
(2 operations per multiply-add, the backward pass twice the forward) and
causal attention's scores and weighted sums, with nothing counted for
recomputation.

Per token, the active matrix-product weights are each layer's attention
(wq d x H(dn + dr), kv_a d x (r + dr), kv_b r x H(dn + dv), wo H dv x d),
the dense layers' SwiGLU (3 d f), each MoE layer's shared experts
(3 d n_shared f_e), its router (d E) and the routed experts a token
reaches on this chip: k held/E experts of 3 d f_e, the share of its top-k
that the held experts take when the routing spreads evenly, and the
head (d V). The embedding is a gather. Attention, per token and layer
at sequence length S (the configuration's ``train_seq_len``, its cell's
traffic): 2 H (dn + dr + dv) S/2 forward (a query meets S/2 keys on
average under the causal mask), three times that with the backward.
"""


def _moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def routed_rows_per_token(cfg: dict) -> float:
    """(token, held expert) rows per token and MoE layer: the top-k's
    share that falls on the held experts under even routing."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["published"]["n_routed_experts"]


def attention_weights(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def expert_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_weights(cfg: dict) -> float:
    """Active matrix-product weights per token."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
    moe = (cfg["n_shared_experts"] * expert_weights(cfg)
           + d * cfg["published"]["n_routed_experts"]
           + routed_rows_per_token(cfg) * expert_weights(cfg))
    return (cfg["num_hidden_layers"] * attention_weights(cfg) + dense
            + _moe_layers(cfg) * moe + d * cfg["vocab_size"])


def attention_ops_per_token(cfg: dict) -> int:
    h = cfg["num_attention_heads"]
    per_key = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    return cfg["num_hidden_layers"] * 3 * 2 * h * per_key \
        * cfg["train_seq_len"] // 2


def flops_per_token(cfg: dict) -> float:
    return 6 * matmul_weights(cfg) + attention_ops_per_token(cfg)


def expert_flops_per_step(cfg: dict, tokens: int) -> float:
    """The held experts' grouped products in one step, forward and
    backward: three products (gate, up, down) of 2 d f_e operations per
    row, three times over, for the rows the routing is built to give."""
    rows = tokens * routed_rows_per_token(cfg) * _moe_layers(cfg)
    return 3 * 3 * 2 * rows * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]
