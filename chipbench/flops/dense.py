"""Model FLOPs of a dense decoder, per trained token.

The convention: 6 x the matrix parameters (each is used once forward and
twice backward, 2 FLOPs a multiply-add), with the embedding lookup left out
and the output head counted, plus 12 x layers x attention width x sequence
for the scores and their weighted sum, forward and backward, over the full
square (the program computes the masked half too).  Recomputation is not
counted.
"""


def matmul_params(cfg: dict) -> int:
    d, f, dh = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    hq, hkv = cfg["n_heads"], cfg["n_kv_heads"]
    attn = 2 * d * hq * dh + 2 * d * hkv * dh
    mlp = (2 if cfg["mlp"] == "gelu" else 3) * d * f
    return cfg["n_layers"] * (attn + mlp) + d * cfg["vocab"]


def flops_per_token(cfg: dict, seq: int) -> float:
    width = cfg["n_heads"] * cfg["head_dim"]
    return 6.0 * matmul_params(cfg) + 12.0 * cfg["n_layers"] * width * seq
