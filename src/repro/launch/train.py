"""Training launcher.

All parameter-gather and gradient-sync collectives run through the
CommEngine (core/comm.py): the flags below select its GatherPolicy
(topology / wire dtype / double-buffered prefetch) and SyncPolicy, or
``--policy auto`` delegates the choice to the link-model autotuner
(core/autotune.py) over ``--link-profile``.

Examples:
  # runnable on this host (reduced config, every device found):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50

  # autotuned policies for an EFA-style network:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --policy auto --link-profile efa-100g

  # production lowering check for the full config (no execution):
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-110b \
      --shape train_4k --mesh multi --policy auto
"""

from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, smoke_variant
from repro.configs.base import ArchConfig
from repro.core import memplan
from repro.core.autotune import cost_hop2_schedule, resolve_config
from repro.core.comm import CommEngine, policies_from_config
from repro.core.linkmodel import get_profile
from repro.core.mics import MiCSConfig
from repro.core.schedule import plan_boundary
from repro.core.topology import MiCSTopology, elastic_host_topology
from repro.data.pipeline import DataConfig
from repro.models import lm
from repro.models.build import build_model
from repro.models.lm import ModelDef
from repro.optim.adamw import OptConfig
from repro.runtime.train_loop import LoopConfig, LoopStats, train


@dataclasses.dataclass(frozen=True)
class TrainRun:
    """Everything ``runtime.train_loop.train`` takes, built in one place."""

    model: ModelDef
    topo: MiCSTopology
    mcfg: MiCSConfig
    oc: OptConfig
    dc: DataConfig
    lc: LoopConfig

    def train(self) -> LoopStats:
        return train(self.model, self.topo, self.mcfg, self.oc, self.dc,
                     self.lc)


def build_training(cfg: ArchConfig, topo: MiCSTopology, mcfg: MiCSConfig, *,
                   steps: int, global_batch: int, seq: int, lr: float,
                   checkpoint_dir: str, checkpoint_every: int,
                   max_step_retries: int = LoopConfig.max_step_retries
                   ) -> TrainRun:
    """The one construction path of a training run (this launcher and
    ``chip_smoke.py``): model, resolved comm policy, optimizer, data and
    loop configs.  Prints the autotune table (``policy='auto'``), the
    boundary plan and the memory plan."""
    model = build_model(cfg, tp=topo.model_size)
    mcfg, plan = resolve_config(mcfg, model, topo, mode="train")
    if plan is not None:
        print(plan.table())
    bplan = plan_boundary(model, topo, mode=mcfg.boundary_schedule,
                          bucket_mb=mcfg.hop2_bucket_mb,
                          clip_mode=mcfg.clip_mode)
    profile = get_profile(mcfg.link_profile)  # name or instance
    hop2 = cost_hop2_schedule(
        model, topo, profile,
        CommEngine.from_config(topo, mcfg).sync_policy,
        boundary=mcfg.boundary_schedule, bucket_mb=mcfg.hop2_bucket_mb,
        clip_mode=mcfg.clip_mode)
    print(f"boundary: {mcfg.boundary_schedule} x {bplan.n_buckets} buckets "
          f"({mcfg.hop2_bucket_mb:g} MB, clip={bplan.clip_mode}) — "
          f"modeled hop-2 {hop2['t_exposed_s']*1e6:.0f}us exposed / "
          f"{hop2['t_total_s']*1e6:.0f}us total on {profile.name}")
    gp, sp = policies_from_config(mcfg)
    lb = max((global_batch // mcfg.micro_steps) // topo.data_parallel_size, 0)
    mem = memplan.predict_footprint(
        model, topo, gp, sp, micro_steps=mcfg.micro_steps, mode="train",
        local_batch=lb, seq=seq, boundary=mcfg.boundary_schedule,
        hop2_bucket_mb=mcfg.hop2_bucket_mb, offload_opt=mcfg.offload_opt)
    routes = " ".join(f"{name}={route}" for name, route
                      in lm.train_routes(model, gp).items())
    paths = lm.attention_paths(model, seq)
    print(f"memplan: {mem.total_gb:.3f} GiB predicted per device "
          f"(carry_offload={mcfg.carry_offload}, "
          f"offload_opt={mcfg.offload_opt}) routes: {routes} "
          f"attention: kernel={paths['kernel']} jnp={paths['jnp']}")
    oc = OptConfig(lr_max=lr, total_steps=steps,
                   warmup_steps=max(steps // 20, 1))
    dc = DataConfig(vocab=cfg.vocab, seq=seq, global_batch=global_batch,
                    micro_steps=mcfg.micro_steps)
    lc = LoopConfig(total_steps=steps, checkpoint_every=checkpoint_every,
                    checkpoint_dir=checkpoint_dir,
                    max_step_retries=max_step_retries)
    return TrainRun(model, topo, mcfg, oc, dc, lc)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--micro-steps", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-dir", default="checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--policy", choices=["manual", "auto"], default="manual",
                    help="'auto' picks gather topology / staging / wire "
                         "dtype from --link-profile via core/autotune.py")
    ap.add_argument("--link-profile", default="v5e",
                    help="link table for --policy auto (v5e, efa-100g, "
                         "efa-400g, or a registered custom profile)")
    ap.add_argument("--gather-order", default="inner_first",
                    choices=["inner_first", "outer_first"],
                    help="staged-gather order (CommEngine GatherPolicy): "
                         "reorder-free 2-stage vs paper-faithful 3-stage")
    ap.add_argument("--no-hierarchical", action="store_true",
                    help="one flat collective over the partition group "
                         "instead of staged gathers")
    ap.add_argument("--quant-gather", action="store_true",
                    help="int8 blockwise wire gathers (GatherPolicy "
                         "wire_dtype='int8'; under --policy auto this "
                         "*permits* rather than forces int8)")
    ap.add_argument("--hop1-wire-dtype", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="hop-1 gradient reduce-scatter wire: fp32 = the "
                         "exact staged adjoint, int8 = ZeRO++-qgZ "
                         "block-quantized stages with fp32 accumulation "
                         "(under --policy auto this permits int8 hop-1)")
    ap.add_argument("--prefetch", type=int, default=1,
                    help="1 = double-buffered lookahead gathers (default), "
                         "0 = serial reference schedule")
    ap.add_argument("--carry-offload", default="none",
                    choices=["none", "host"],
                    help="'host' keeps each layer's gathered buffer for the "
                         "backward in host memory (d2h in the forward, h2d "
                         "in the backward, core/hostoffload.py) instead of "
                         "re-gathering it in the backward; priced on the "
                         "link model's host tier")
    ap.add_argument("--offload-opt", action="store_true",
                    help="host-offload the AdamW m/v shards: the state dict "
                         "keeps only params+step, moments stream through "
                         "the host stash around the boundary update "
                         "(bitwise-identical params trajectory)")
    ap.add_argument("--clip-mode", default="exact",
                    choices=["exact", "approx"],
                    help="boundary global-norm clip: 'exact' is the "
                         "barriered reference; 'approx' pipelines each "
                         "bucket's AdamW under the next bucket's hop-2 "
                         "with a one-bucket-stale clip factor "
                         "(core/schedule.py; under --policy auto this "
                         "permits rather than forces approx)")
    ap.add_argument("--hbm-budget-gb", type=float, default=0,
                    help="per-device HBM budget in GiB: the memory planner "
                         "gates --policy auto candidates on it and falls "
                         "back to the remat carry when the stored one "
                         "does not fit; 0 = no budget")
    ap.add_argument("--boundary-schedule", default="bucketed",
                    choices=["serial", "bucketed"],
                    help="gradient-accumulation boundary: bucketed hop-2 "
                         "software pipeline (core/schedule.py) or the "
                         "monolithic serial reference — bitwise identical")
    ap.add_argument("--hop2-bucket-mb", type=float, default=32.0,
                    help="hop-2 pipeline bucket size in fp32-gradient MB "
                         "(--policy auto ranks this axis itself)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    mcfg = MiCSConfig(micro_steps=args.micro_steps,
                      hierarchical=not args.no_hierarchical,
                      gather_order=args.gather_order,
                      quant_gather=args.quant_gather,
                      hop1_wire_dtype=args.hop1_wire_dtype,
                      prefetch=bool(args.prefetch),
                      carry_offload=args.carry_offload,
                      offload_opt=args.offload_opt,
                      clip_mode=args.clip_mode,
                      policy=args.policy,
                      link_profile=args.link_profile,
                      boundary_schedule=args.boundary_schedule,
                      hop2_bucket_mb=args.hop2_bucket_mb,
                      hbm_budget_gb=args.hbm_budget_gb or None)
    n = len(jax.devices())   # one partition group over every device found
    run = build_training(
        cfg, elastic_host_topology(n, n), mcfg, steps=args.steps,
        global_batch=args.global_batch, seq=args.seq, lr=args.lr,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every)
    stats = run.train()
    print(f"final loss {stats.losses[-1]:.4f} over {len(stats.losses)} steps; "
          f"restarts={stats.restarts}")


if __name__ == "__main__":
    main()
