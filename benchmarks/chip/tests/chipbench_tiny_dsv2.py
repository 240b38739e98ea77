"""A copy of the benchmark whose DeepSeek-V2 configuration is cut to the
tiny deepseek-v2-lite-reduced widths (d_model 64, 4 heads, a router of 4
experts with 2 held, top-2), with its traffic at 2 x 64 tokens a step,
for the CPU rehearsal tests. The other configurations and traffic mixes
are the repository's as they are: a test here runs the DeepSeek-V2 cell
only."""
import json
from pathlib import Path

from chipbench_tiny import ROOT, RUN_CONFIG, run  # noqa: F401 (re-export)
from benchmarks.chip import spec

CONFIG = "deepseek-v2-lite-5l-e8-v12.8k"
CELL = "dsv2lite-5l.steady"
TRAFFIC = "steady-4k"
TINY = dict(registry="deepseek-v2-lite-reduced", hidden_size=64,
            intermediate_size=64, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            n_shared_experts=1, num_experts_per_tok=2, n_routed_experts=2,
            num_hidden_layers=3, vocab_size=512, train_seq_len=64)


def tiny_config() -> dict:
    cfg = json.loads((ROOT / "benchmarks" / "chip" / "configs"
                      / f"{CONFIG}.json").read_text())
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], n_routed_experts=4)
    cfg["reference"] = dict(cfg["reference"], query_block=16)
    return cfg


def make_root(tmp: Path, cfg: dict = None) -> spec.Bench:
    """BENCHMARK.json with the DeepSeek-V2 configuration (``cfg``, by
    default ``tiny_config()``) and its traffic cut to the tiny size,
    under ``tmp``; the code is the repository's."""
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in doc["configs"]:
        if c["name"] == CONFIG:
            c["file"] = f"configs/{CONFIG}.json"
            (tmp / "configs").mkdir(exist_ok=True)
            (tmp / c["file"]).write_text(json.dumps(cfg or tiny_config()))
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    tdir = tmp / "benchmarks" / "chip" / "traffic"
    tdir.mkdir(parents=True)
    t = json.loads((ROOT / "benchmarks" / "chip" / "traffic"
                    / f"{TRAFFIC}.json").read_text())
    t.update(batch=2, seq=64)
    (tdir / f"{TRAFFIC}.json").write_text(json.dumps(t))
    return spec.Bench(tmp)
