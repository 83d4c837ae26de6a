"""Drive the program's dense training path for a benchmark cell.

Builds the run through ``launch.train.build_training``, its step through
``core.mics.build_train_step`` and its state through
``core.mics.init_state``, then hands the step the benchmark's own weights
(the reference's draws, packed into the program's flat pools), and reads
the program's state back under the reference's leaf names.  Nothing here
computes the model: it only places and reads the program's buffers.
"""

from __future__ import annotations

import tempfile

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import dense as ref
from repro.configs.base import ArchConfig
from repro.core.mics import MiCSConfig, build_train_step, init_state
from repro.core.topology import elastic_host_topology
from repro.launch.train import build_training
from repro.optim.adamw import OptConfig

# configuration keys the program's ArchConfig takes as they are
ARCH_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab", "mlp", "norm", "use_rope", "rope_theta",
             "qkv_bias", "tie_embeddings", "max_seq")


def arch_config(cfg: dict) -> ArchConfig:
    return ArchConfig(name=cfg["name"], family="dense",
                      **{k: cfg[k] for k in ARCH_KEYS})


def _leaf(cfg: dict, pool: str, layer: int, seg: str) -> str:
    """The reference's name for one program segment of one layer."""
    if pool == "embed":
        return {"emb.table": "embed"}[seg]
    if pool == "head":
        return {"final.scale": "final_g", "final.bias": "final_b",
                "head.w": "head"}[seg]
    sub, kind = seg.split(".")
    if sub in ("ln1", "ln2"):
        name = f"{sub}_{'g' if kind == 'scale' else 'b'}"
    elif kind == "wd" and cfg["mlp"] == "gelu":
        name = "w2"
    else:
        name = kind
    return f"layers.{layer}.{name}"


def _gain_offset(seg: str) -> float:
    """The program stores a norm's gain g as g - 1."""
    return 1.0 if seg.endswith(".scale") else 0.0


class Program:
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
        # The optimizer as the cell states it, not the launcher's defaults.
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

    # -- weights -----------------------------------------------------------
    def _pack(self, key) -> dict[str, jax.Array]:
        """The reference's initial weights in the program's flat pools,
        ``[stack, 1, flat_len]`` each."""
        out = {}
        for pool in self.model.all_pools():
            lay = pool.layout
            rows = []
            for i in range(pool.stack):
                parts = [ref.draw_flat(self.cfg, key,
                                       _leaf(self.cfg, pool.name, i, s.name),
                                       s.size) - _gain_offset(s.name)
                         for s in lay.segments]
                parts.append(jnp.zeros((lay.flat_len - lay.raw_len,),
                                       jnp.float32))
                rows.append(lax.optimization_barrier(jnp.concatenate(parts)))
            out[pool.name] = jnp.stack(rows)[:, None, :]
        return out

    def init_state(self, seed: int) -> dict:
        """The program's own set-up: ``core.mics.init_state`` draws the
        step's state and places it on the mesh."""
        return init_state(self.model, self.topo, seed % 2**31)

    def load_weights(self, state: dict, key) -> dict:
        """``state`` with its parameters replaced by the reference's initial
        weights, drawn from ``key`` in one jitted call and placed in the
        same shardings.  The moments stay as the program made them."""
        shardings = jax.tree.map(lambda a: a.sharding, state["params"])
        for a in jax.tree.leaves(state["params"]):
            a.delete()
        return {**state,
                "params": jax.device_put(self._packed(key), shardings)}

    # -- readings ----------------------------------------------------------
    def _norms(self, pools: dict) -> dict[str, jax.Array]:
        """Each segment's norm per layer, ``{pool: [stack, segments]}``."""
        out = {}
        for pool in self.model.all_pools():
            x = pools[pool.name][:, 0]
            out[pool.name] = jnp.stack(
                [jnp.sqrt(jnp.sum(jnp.square(x[:, s.offset:s.end]), axis=1))
                 for s in pool.layout.segments], axis=1)
        return out

    def _by_leaf(self, norms: dict) -> dict[str, float]:
        norms = jax.device_get(norms)
        out = {}
        for pool in self.model.all_pools():
            for i in range(pool.stack):
                for j, s in enumerate(pool.layout.segments):
                    out[_leaf(self.cfg, pool.name, i, s.name)] = float(
                        norms[pool.name][i, j])
        return out

    def grad_norms(self, state) -> dict[str, float]:
        """Each leaf's norm of the gradient AdamW was given, read from its
        first moment after the first step (m = (1 - b1) g)."""
        return {k: v / (1 - self.oc.b1)
                for k, v in self._by_leaf(self._segment_norms(state["m"]))
                .items()}

    def delta_norms(self, state, key) -> dict[str, float]:
        """Each leaf's norm of its change since the seed's weights."""
        return self._by_leaf(self._delta_norms(state["params"], key))
