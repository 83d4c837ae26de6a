"""Tiny cells for the CPU tests: the benchmark's two dense families at
widths a test can hold, on one device or a repl=2 x shard=2 mesh."""

import json

from chipbench import cell as cellmod

BERT = dict(name="bert-tiny", family="dense", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128, vocab=256,
            mlp="gelu", norm="ln", use_rope=False, rope_theta=500000.0,
            qkv_bias=False, tie_embeddings=False, max_seq=64)
YI = dict(name="yi-tiny", family="dense", n_layers=2, d_model=64,
          n_heads=4, n_kv_heads=2, head_dim=32, d_ff=96, vocab=256,
          mlp="swiglu", norm="rms", use_rope=True, rope_theta=10000.0,
          qkv_bias=False, tie_embeddings=False, max_seq=64)
CONFIGS = {"bert": BERT, "yi": YI}


def traffic(chips: int = 1, shard: int = 1) -> dict:
    with open(cellmod.HERE / "traffic" / "s512_b8_m2.json") as f:
        tr = json.load(f)
    tr.update(global_batch=4 * chips, seq=32, chips=chips,
              mesh={"repl": chips // shard, "shard": shard},
              distinct_batches=2, trace_steps=2)
    return tr


def cell(kind: str, chips: int = 1, shard: int = 1,
         limits: dict | None = None) -> cellmod.Cell:
    lim = limits or {"loss_gap": 1.0, "grad_gap": 1.0, "update_gap": 1.0}
    return cellmod.Cell(f"{kind}-tiny-{chips}", chips, CONFIGS[kind],
                        traffic(chips, shard), lim, ())
