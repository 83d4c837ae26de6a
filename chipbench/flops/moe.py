"""Model FLOPs of a DeepSeek-V2 decoder holding a share of its experts, per
trained token, and the work of the held experts' grouped products.

The convention of ``flops/dense.py``: 6 x the matrix parameters a token
uses (the embedding lookup left out, the output head counted), plus 12 x
layers x attention width x sequence, over the full square; recomputation
not counted.  Here:

* a token uses the router, the shared experts and ``num_experts_per_tok``
  x held / routed of the held experts: 0.75 expert applications with 8 of
  64 held and top-6, the expected share under even routing;
* MLA's matrices are Wq, Wkv_a, Wkv_b and Wo; its attention width is heads
  x (qk head size + v head size) / 2, since the scores are qk wide and the
  weighted sum v wide.
"""

from chipbench.reference.moe import routed


def _attention(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def _params(cfg: dict, experts: float) -> float:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    per_moe = d * routed(cfg) + 3 * d * f * (experts + cfg["n_shared_experts"])
    return (cfg["num_hidden_layers"] * _attention(cfg)
            + dense * 3 * d * cfg["intermediate_size"] + moe * per_moe
            + d * cfg["vocab_size"])


def matmul_params(cfg: dict) -> int:
    """Every matrix parameter the chip holds, the embedding left out."""
    return int(_params(cfg, cfg["n_routed_experts"]))


def expert_applications(cfg: dict) -> float:
    """Held experts a token is routed to, in expectation."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / routed(cfg))


def flops_per_token(cfg: dict, seq: int) -> float:
    width = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) / 2
    return (6.0 * _params(cfg, expert_applications(cfg))
            + 12.0 * cfg["num_hidden_layers"] * width * seq)


def expert_matmul_flops(cfg: dict, rows: float) -> float:
    """One grouped product over ``rows`` assignments to the held experts
    (a row times one expert's d x f matrix, or f x d)."""
    return 2.0 * rows * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_matmul_bytes(cfg: dict, rows: float, itemsize: int = 2) -> float:
    """Least HBM traffic of one such product: its rows in, the held
    experts' matrices, its rows out."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return itemsize * (rows * (d + f) + cfg["n_routed_experts"] * d * f)
