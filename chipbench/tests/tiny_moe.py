"""A tiny DeepSeek-V2 cell for the CPU tests: the expert configuration's
structure (a leading dense layer, then expert layers holding 4 of 16 routed
experts with top-3 routing and 2 shared experts; MLA with YaRN rope) at
widths a test can hold."""

import json

from chipbench import cell as cellmod
from chipbench.tests import tiny


def config(**over) -> dict:
    with open(cellmod.HERE / "configs" / "deepseek-v2-lite-l5-e8.json") as f:
        cfg = json.load(f)
    cfg.update(
        name="dsv2-tiny", hidden_size=64, d_model=64, intermediate_size=96,
        kv_lora_rank=16, num_attention_heads=4, num_key_value_heads=4,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        moe_intermediate_size=24, n_routed_experts=4, num_experts_per_tok=3,
        num_hidden_layers=3, vocab_size=256, vocab=256,
        max_position_embeddings=64,
        rope_scaling={**cfg["rope_scaling"],
                      "original_max_position_embeddings": 16},
        published={"num_hidden_layers": 3, "n_routed_experts": 16,
                   "vocab_size": 256})
    cfg.update(over)
    return cfg


def cell(chips: int = 1, shard: int = 1, limits: dict | None = None,
         **over) -> cellmod.Cell:
    lim = limits or {"loss_gap": 1.0, "grad_gap": 1.0, "update_gap": 1.0}
    return cellmod.Cell(f"dsv2-tiny-{chips}", chips, config(**over),
                        tiny.traffic(chips, shard), lim, ())
