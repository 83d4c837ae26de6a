"""Drive the program's expert-layer training path for a benchmark cell.

As ``program/dense.py``, through ``launch.train.build_training``,
``core.mics.build_train_step`` and ``core.mics.init_state``, for a
DeepSeek-V2 configuration file (its Hugging Face keys): it maps the file
onto the program's ``ArchConfig``, packs the reference's weights
(``reference/moe.py``) into the program's flat pools, and reads them back
under the reference's leaf names.  Nothing here computes the model.
"""

from __future__ import annotations

import tempfile

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.program import dense
from chipbench.reference import moe as ref
from repro.configs.base import ArchConfig
from repro.core.mics import MiCSConfig, build_train_step
from repro.core.topology import elastic_host_topology
from repro.launch.train import build_training
from repro.optim.adamw import OptConfig

# what the program computes of DeepSeek-V2; a file asking for more is refused
FIXED = {"hidden_act": "silu", "q_lora_rank": None, "scoring_func": "softmax",
         "topk_method": "greedy", "routed_scaling_factor": 1, "n_group": 1,
         "topk_group": 1, "moe_layer_freq": 1, "rms_norm_eps": 1e-6}


def arch_config(cfg: dict) -> ArchConfig:
    bad = {k: cfg[k] for k, v in FIXED.items() if cfg[k] != v}
    if bad or cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"{cfg['name']}: the program does not compute {bad}")
    ys = cfg["rope_scaling"]
    return ArchConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"], mlp="swiglu", norm="rms",
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        n_experts=ref.routed(cfg), experts_held=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        router_aux_weight=cfg["router_aux_weight"],
        first_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], yarn_factor=float(ys["factor"]),
        yarn_original_max_pos=ys["original_max_position_embeddings"],
        yarn_beta_fast=float(ys["beta_fast"]),
        yarn_beta_slow=float(ys["beta_slow"]),
        yarn_mscale=ys["mscale"], yarn_mscale_all_dim=ys["mscale_all_dim"],
        max_seq=cfg["max_position_embeddings"])


# program segment -> reference leaf, within a layer
LAYER_LEAVES = {
    "ln1.scale": "ln1_g", "ln2.scale": "ln2_g", "attn.wq": "wq",
    "attn.wkv_a": "wkv_a", "attn.kv_norm.scale": "kv_norm_g",
    "attn.wkv_b": "wkv_b", "attn.wo": "wo", "mlp.wg": "wg", "mlp.wu": "wu",
    "mlp.wd": "wd", "router.w": "router", "moe.wg": "ewg", "moe.wu": "ewu",
    "moe.wd": "ewd", "shared.wg": "swg", "shared.wu": "swu",
    "shared.wd": "swd"}


def _leaf(cfg: dict, pool: str, layer: int, seg: str) -> str:
    """The reference's name for one program segment of one layer: the
    ``dense`` pool holds the leading dense layers, ``layers`` the rest."""
    if pool == "embed":
        return {"emb.table": "embed"}[seg]
    if pool == "head":
        return {"final.scale": "final_g", "head.w": "head"}[seg]
    first = cfg["first_k_dense_replace"] if pool == "layers" else 0
    return f"layers.{first + layer}.{LAYER_LEAVES[seg]}"


class Program(dense.Program):
    """One cell's program: model, mesh, optimizer and jitted step."""

    def __init__(self, cfg: dict, traffic: dict, opt: dict):
        self.cfg = cfg
        chips, shard = traffic["chips"], traffic["mesh"]["shard"]
        if traffic["mesh"]["repl"] * shard != chips:
            raise ValueError(f"mesh {traffic['mesh']} is not {chips} chips")
        run = build_training(
            arch_config(cfg), elastic_host_topology(chips, shard),
            MiCSConfig(micro_steps=traffic["micro_steps"]),
            steps=opt["total_steps"], global_batch=traffic["global_batch"],
            seq=traffic["seq"], lr=opt["lr"],
            checkpoint_dir=tempfile.gettempdir(), checkpoint_every=0)
        self.model, self.topo, self.mcfg = run.model, run.topo, run.mcfg
        self.oc = OptConfig(
            lr_max=opt["lr"], lr_min_ratio=opt["lr_min_ratio"],
            warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"])
        self.step = build_train_step(self.model, self.topo, run.mcfg, self.oc)
        self._packed = jax.jit(self._pack)
        self._segment_norms = jax.jit(self._norms)
        self._delta_norms = jax.jit(
            lambda params, key: self._norms(
                jax.tree.map(jnp.subtract, params, self._pack(key))))

    def _pack(self, key) -> dict[str, jax.Array]:
        """The reference's initial weights in the program's flat pools,
        ``[stack, 1, flat_len]`` each; a norm's gain g is stored as g - 1."""
        out = {}
        for pool in self.model.all_pools():
            lay = pool.layout
            rows = []
            for i in range(pool.stack):
                parts = [ref.draw_flat(self.cfg, key,
                                       _leaf(self.cfg, pool.name, i, s.name),
                                       s.size)
                         - (1.0 if s.name.endswith(".scale") else 0.0)
                         for s in lay.segments]
                parts.append(jnp.zeros((lay.flat_len - lay.raw_len,),
                                       jnp.float32))
                rows.append(lax.optimization_barrier(jnp.concatenate(parts)))
            out[pool.name] = jnp.stack(rows)[:, None, :]
        return out

    def _by_leaf(self, norms: dict) -> dict[str, float]:
        norms = jax.device_get(norms)
        return {_leaf(self.cfg, pool.name, i, s.name):
                float(norms[pool.name][i, j])
                for pool in self.model.all_pools()
                for i in range(pool.stack)
                for j, s in enumerate(pool.layout.segments)}
