"""Operations one rwkv6 training step requires, from the configuration's
sizes: the forward and backward passes' matrix products (2 operations
per multiply-add, the backward pass twice the forward) and the wkv
recurrence's own arithmetic, with nothing counted for recomputation.

Per token and layer, the matrix products' weights are the time mix's
receptance, key, value, gate and output projections (5 d^2), its two
low-rank token-shift maps (2 x 5 m d) and decay maps (2 e d), and the
channel mix's key, value and receptance maps (2 d f + d^2); the head
adds d V. The embedding is a gather. The recurrence, per head of size n:
k v^T (n^2), u * kv (n^2), S + u kv (n^2), r . (...) (2 n^2), w * S
(n^2), + kv (n^2): 7 n^2 forward, 21 n^2 with its backward pass.
"""


def matmul_weights(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    m, e = cfg["time_mix_extra_dim"], cfg["time_decay_extra_dim"]
    layer = 5 * d * d + 2 * 5 * m * d + 2 * e * d + 2 * d * f + d * d
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def wkv_ops_per_token(cfg: dict) -> int:
    n = cfg["head_size"]
    heads = cfg["hidden_size"] // n
    return cfg["num_hidden_layers"] * heads * 21 * n * n


def flops_per_token(cfg: dict) -> int:
    return 6 * matmul_weights(cfg) + wkv_ops_per_token(cfg)
