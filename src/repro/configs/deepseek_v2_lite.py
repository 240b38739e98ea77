"""DeepSeek-V2-Lite — latent attention without a query low-rank, YaRN
rope, fine-grained MoE: 2 shared + 64 routed experts, top-6.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json] 27
layers, d_model 2048, 16 heads, vocab 102400 (untied), RMSNorm eps 1e-6.
Layer 0 is dense (SwiGLU, width 10944); layers 1-26 are MoE.

Every layer's attention (MLA, no biases), for x of width 2048:

    q = x Wq                          Wq: 2048 x 16 (128 + 64)
    [c_kv | k_pe] = x W_kva           W_kva: 2048 x (512 + 64)
    c_kv <- RMSNorm(c_kv)             its own gain
    [k_nope | v] = c_kv W_kvb         W_kvb: 512 x 16 (128 + 128)
    q = [q_nope | rope(q_pe)], k = [k_nope | rope(k_pe)]  (k_pe: one key
                                      shared by the 16 heads)
    o = softmax(q k^T / tau + causal) v,   out = o Wo   Wo: 2048 x 2048

YaRN rope over the 64 rope dims, base 10000, factor 40, original
context 4096, beta_fast 32, beta_slow 1, mscale = mscale_all_dim =
0.707: inv_extra_i = base^(-2i/64), inv_inter_i = inv_extra_i / 40;
corr(n) = 64 ln(4096 / (2 pi n)) / (2 ln base), low = floor(corr(32)) =
10, high = ceil(corr(1)) = 23; ramp_i = clip((i - low)/(high - low), 0,
1); inv_freq = inv_inter ramp + inv_extra (1 - ramp). cos/sin are scaled
by 1 (mscale = mscale_all_dim); m = 0.1 * 0.707 ln 40 + 1 = 1.260804 and
1/tau = m^2 / sqrt(192) = 0.114721. The program rotates the halves of
the rope dims as pairs, where the source rotates interleaved pairs: on
random weights a fixed permutation of 64 columns of Wq and W_kva.

Each MoE layer, routed over all 64 experts:

    p = softmax(x W_r)                W_r: 2048 x 64
    top-6 of p (greedy), gates g = p  (norm_topk_prob false, scale 1)
    y = sum_{e in top6} g_e SwiGLU_e(x) + SwiGLU_shared(x)
                                      experts of width 1408; the shared
                                      one 2 x 1408 = 2816
    aux = alpha E/(K T) sum_i count_i mean_t p_{t,i}   per sequence,
                                      alpha = 0.001 (aux_loss_alpha)
"""
from repro.configs.base import (ArchConfig, AttnSpec, LayerSpec, MLASpec,
                                MLPSpec, MoESpec, Stage, YarnRope)

ATTN = AttnSpec(
    n_heads=16, n_kv_heads=16, head_dim=128, rope=True, rope_theta=10_000.0,
    mla=MLASpec(q_lora_rank=None, kv_lora_rank=512, qk_nope_dim=128,
                qk_rope_dim=64, v_head_dim=128),
    rope_scaling=YarnRope(factor=40.0, original_max_pos=4096,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707))


def config() -> ArchConfig:
    dense0 = LayerSpec(kind="attn", attn=ATTN,
                       mlp=MLPSpec(kind="dense", d_ff=10_944, act="swiglu"))
    moe = LayerSpec(
        kind="attn", attn=ATTN,
        mlp=MLPSpec(kind="moe", act="swiglu",
                    moe=MoESpec(n_experts=64, top_k=6, d_expert=1408,
                                n_shared=2, norm_topk=False)))
    return ArchConfig(
        name="deepseek-v2-lite",
        family="moe",
        d_model=2048,
        vocab_size=102_400,
        stages=(Stage(block=(dense0,), repeat=1),
                Stage(block=(moe,), repeat=26)),
        norm="rmsnorm",
        norm_eps=1e-6,
        tie_embeddings=False,
        max_seq=163_840,
        sub_quadratic=False,
        moe_aux_coef=0.001,
    )
