"""rwkv6-1.6b cut to one v5e chip's share: 2 of its 24 layers and a
quarter of its vocabulary.

source: arXiv:2404.05892 (RWKV-6 "Finch" 1.6B) — 24L, d_model=2048,
  32 wkv heads of 64, channel-mix d_ffn=7168, vocab=65536.
reduced: depth 24 -> 2; vocab 65536 -> 16384. Every other width is the
  published one.
deployment (assumed; the source states no training layout): 12 pipeline
  stages of 2 layers, the embedding and LM head split by vocabulary rows
  over 4 chips; this chip holds one stage and one vocabulary quarter.
  The traffic draws its token ids from the quarter, and the loss is over
  it.
why: the chip smoke saves this state twice through a 3-node simulated
  Assise cluster on one host, which writes about 6.5x the state to disk
  per save (log, two replica slots, three hot areas). The chip host
  allows 45 GiB of disk writes per run, so the state must stay near
  2 GB: 0.178 B parameters, f32 weights + AdamW m and v = 2.14 GB.
"""
import dataclasses

from repro.configs import rwkv6_1_6b
from repro.configs.base import ArchConfig, Stage

LAYERS = 2
VOCAB_SHARE = 65_536 // 4


def config() -> ArchConfig:
    full = rwkv6_1_6b.config()
    (stage,) = full.stages
    return dataclasses.replace(
        full, name="rwkv6-1.6b-2l-v16k", vocab_size=VOCAB_SHARE,
        stages=(Stage(block=stage.block, repeat=LAYERS),))
