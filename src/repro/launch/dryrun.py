"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent on the
production mesh — (16,16) single pod and (2,16,16) two pods — and records
``memory_analysis()``, ``cost_analysis()`` and the trip-count-weighted
collective census (roofline inputs) to artifacts/dryrun/<cell>.json.

Communication policy: all collectives run through the CommEngine
(core/comm.py).  The manual flags (--gather-order, --quant-gather,
--prefetch, --clip-mode, ...) map 1:1 onto its
GatherPolicy/SyncPolicy; ``--policy auto`` instead hands the choice to the
link-model autotuner (core/autotune.py), which prints the ranked candidate
table for the ``--link-profile`` and records the chosen plan — plus a
predicted-vs-measured cross-check of the plan's per-stage wire bytes
against the compiled HLO census — into the cell artifact.

Memory: every cell records the memory planner's predicted per-device
footprint next to XLA's compiled ``memory_analysis()``
(plan-vs-compiled, core/memplan.py).  ``--hbm-budget-gb`` additionally
applies the paper's §3.1 rule — the minimal partition group whose
predicted footprint fits — when no --partition-size is pinned, and gates
``--policy auto`` candidates on feasibility.  Training cells
additionally record the boundary scheduler's bucket plan
(``--boundary-schedule`` / ``--hop2-bucket-mb``, core/schedule.py) with
the link model's predicted exposed-vs-hidden hop-2 time and the measured
census evidence that hop-2 runs at bucket granularity interleaved with
boundary compute.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-110b --shape train_4k --mesh multi
  python -m repro.launch.dryrun --arch qwen1.5-110b --shape train_4k \
      --mesh multi --policy auto --link-profile efa-100g
  python -m repro.launch.dryrun --all [--mesh both]
"""

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cells, get_config
from repro.core import memplan
from repro.core.autotune import (
    compare_census, cost_hop2_schedule, predict_traffic, resolve_config,
    resolve_scale,
)
from repro.core.comm import CommEngine, policies_from_config
from repro.core.linkmodel import get_profile
from repro.core.mics import (
    MiCSConfig, build_train_step, init_state_shapes, make_batch_shapes,
)
from repro.core.schedule import plan_boundary
from repro.launch.mesh import make_mics_topology
from repro.models.build import active_param_count, build_model, exact_param_count
from repro.optim.adamw import OptConfig
from repro.roofline.hlo_stats import analyze
from repro.runtime.serving import batch_axes_for, build_serve_steps, global_cache_shapes

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

TRAIN_MICRO_STEPS = 4  # paper §5.1.5 setup (s=4 gradient accumulation)


def input_specs(arch: str, shape: str, topo, model):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    spec = SHAPES[shape]
    seq, gb = spec["seq"], spec["global_batch"]
    if spec["kind"] == "train":
        return mics_train_inputs(model, seq, gb)
    if spec["kind"] == "prefill":
        return serve_prefill_inputs(model, topo, seq, gb)
    return serve_decode_inputs(model, topo, seq, gb)


def mics_train_inputs(model, seq, gb):
    return make_batch_shapes(model, gb, seq, TRAIN_MICRO_STEPS)


def serve_prefill_inputs(model, topo, seq, gb):
    sds = jax.ShapeDtypeStruct
    out = {"tokens": sds((gb, seq), jnp.int32)}
    cfg = model.cfg
    if cfg.family == "vlm":
        out["vision"] = sds((gb, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        out["audio"] = sds((gb, cfg.n_audio_frames, cfg.d_model), jnp.bfloat16)
    return out


def serve_decode_inputs(model, topo, seq, gb):
    sds = jax.ShapeDtypeStruct
    baxes = batch_axes_for(topo, gb)
    caches, _ = global_cache_shapes(model, topo, gb, seq, baxes)
    return {
        "tokens": sds((gb, 1), jnp.int32),
        "pos": sds((), jnp.int32),
        "caches": caches,
        "seeds": sds((gb,), jnp.int32),
        "temps": sds((gb,), jnp.float32),
        "row_mask": sds((gb,), jnp.bool_),
    }


def run_cell(arch: str, shape: str, multi_pod: bool, mcfg: MiCSConfig,
             out_dir: pathlib.Path = ART, tag: str = "",
             partition_size: int | None = None, zero3: bool = False,
             tp: int | None = None, serve_footprint: bool = False) -> dict:
    cfg = get_config(arch)
    spec = SHAPES[shape]
    t0 = time.time()
    n_params = exact_param_count(cfg)
    scale_plan = None
    if mcfg.hbm_budget_gb is not None and partition_size is None \
            and not zero3:
        # the paper's §3.1 rule, analytically: minimal partition group
        # whose predicted per-device footprint fits the budget
        # (core/memplan.py).
        sizing_model = build_model(cfg, tp=tp or 16)
        # the partition group is carved from the 16-wide data axis; pods
        # and the dp2 leftover of a narrow tp replicate on top of it
        extra_repl = (2 if multi_pod else 1) * (16 // (tp or 16))
        partition_size, scale_plan = resolve_scale(
            sizing_model, mcfg, data_extent=16,
            mode="train" if spec["kind"] == "train" else "serve",
            extra_replication=extra_repl)
        print(f"memplan: p={partition_size} "
              f"({scale_plan.total_gb:.2f} GiB predicted vs budget "
              f"{mcfg.hbm_budget_gb:g} GiB)", flush=True)
    topo = make_mics_topology(
        multi_pod=multi_pod, param_count=n_params,
        partition_size=partition_size, zero3=zero3, tp=tp,
        state_bytes_per_param=2 if serve_footprint else None)
    model = build_model(cfg, tp=topo.model_size)

    mcfg, plan = resolve_config(
        mcfg, model, topo,
        mode="train" if spec["kind"] == "train" else "serve")
    if plan is not None:
        print(plan.table(), flush=True)
    engine = CommEngine.from_config(topo, mcfg)

    record = {
        "arch": cfg.name, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": spec["kind"], "seq": spec["seq"],
        "global_batch": spec["global_batch"],
        "zero3": zero3,
        "tp": topo.model_size,
        "partition_axes": list(topo.partition_axes),
        "partition_size": topo.partition_size,
        "replication_degree": topo.replication_degree,
        "params": n_params,
        "active_params": active_param_count(cfg),
        "micro_steps": TRAIN_MICRO_STEPS if spec["kind"] == "train" else 1,
        "mics": dataclasses.asdict(mcfg) | {
            "gather_dtype": jnp.dtype(mcfg.gather_dtype).name,
            "link_profile": str(getattr(mcfg.link_profile, "name",
                                        mcfg.link_profile)),
        },
        "comm": engine.describe(),
        "autotune": plan.describe() if plan is not None else None,
        "tag": tag,
    }

    # boundary scheduler: the static bucket plan + the link model's
    # hidden-vs-exposed hop-2 time for it (core/schedule.py, autotune).
    if spec["kind"] == "train":
        bplan = plan_boundary(model, topo, mode=mcfg.boundary_schedule,
                              bucket_mb=mcfg.hop2_bucket_mb,
                              clip_mode=mcfg.clip_mode)
        profile = get_profile(mcfg.link_profile)  # name or instance
        record["boundary"] = bplan.describe() | {
            "predicted": cost_hop2_schedule(
                model, topo, profile, engine.sync_policy,
                boundary=mcfg.boundary_schedule,
                bucket_mb=mcfg.hop2_bucket_mb,
                clip_mode=mcfg.clip_mode),
            "link_profile": profile.name,
        }

    serve_dtype = jnp.bfloat16 if serve_footprint else jnp.float32
    if mcfg.quant_gather:
        from repro.core.quant import BLOCK

        serve_params = {
            name: {
                "q": jax.ShapeDtypeStruct(shape, jnp.int8),
                "s": jax.ShapeDtypeStruct(
                    (*shape[:-1], shape[-1] // BLOCK), jnp.float32),
            }
            for name, shape in model.global_flat_shapes().items()
        }
        record["serve_param_dtype"] = "int8+blockscale"
    else:
        serve_params = {
            name: jax.ShapeDtypeStruct(shape, serve_dtype)
            for name, shape in model.global_flat_shapes().items()
        }
        record["serve_param_dtype"] = str(serve_dtype.__name__)

    if spec["kind"] == "train":
        step = build_train_step(model, topo, mcfg,
                                OptConfig(total_steps=1000))
        state = init_state_shapes(model)
        batch = mics_train_inputs(model, spec["seq"], spec["global_batch"])
        lowered = step.lower(state, batch)
    elif spec["kind"] == "prefill":
        prefill_fn, _ = build_serve_steps(
            model, topo, mcfg, cache_len=spec["seq"],
            batch_axes=batch_axes_for(topo, spec["global_batch"]))
        lowered = prefill_fn.lower(
            serve_params,
            serve_prefill_inputs(model, topo, spec["seq"], spec["global_batch"]))
    else:  # decode
        baxes = batch_axes_for(topo, spec["global_batch"])
        _, decode_fn = build_serve_steps(
            model, topo, mcfg, cache_len=spec["seq"], batch_axes=baxes)
        inp = serve_decode_inputs(model, topo, spec["seq"], spec["global_batch"])
        lowered = decode_fn.lower(
            serve_params, inp["caches"], inp["tokens"], inp["pos"],
            inp["seeds"], inp["temps"], inp["row_mask"])

    record["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    record["compile_s"] = round(time.time() - t1, 1)

    ma = compiled.memory_analysis()
    if ma is not None:
        record["memory_analysis"] = {
            k: getattr(ma, k)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes")
            if hasattr(ma, k)
        }
    # memory planner: predicted per-device footprint vs the compiled
    # analysis (plan-vs-compiled, core/memplan.py) for every cell.
    micro = record["micro_steps"]
    lb = max((spec["global_batch"] // micro) // topo.data_parallel_size, 0)
    gp_, sp_ = policies_from_config(mcfg)
    mem_plan = memplan.predict_footprint(
        model, topo, gp_, sp_, micro_steps=micro,
        mode="train" if spec["kind"] == "train" else "serve",
        local_batch=lb, seq=spec["seq"],
        boundary=mcfg.boundary_schedule,
        hop2_bucket_mb=mcfg.hop2_bucket_mb)
    record["memplan"] = mem_plan.describe()
    record["memplan"]["hbm_budget_gb"] = mcfg.hbm_budget_gb
    if scale_plan is not None:
        record["memplan"]["resolved_partition_size"] = topo.partition_size
    if ma is not None and hasattr(ma, "temp_size_in_bytes"):
        meas = (ma.argument_size_in_bytes + ma.temp_size_in_bytes)
        record["memplan"]["compiled_total_bytes"] = meas
        record["memplan"]["plan_vs_compiled_ratio"] = (
            mem_plan.total_bytes / meas if meas else None)
    ca = compiled.cost_analysis()
    # NB: XLA's cost analysis visits while bodies ONCE (no trip weighting);
    # kept raw for reference.  The roofline uses the trip-weighted stats.
    record["cost_analysis_raw"] = {
        k: ca[k] for k in ("flops", "bytes accessed", "transcendentals")
        if k in ca
    }

    mesh_shape = dict(zip(topo.mesh.axis_names,
                          topo.mesh.devices.shape))
    record["stats"] = analyze(
        compiled.as_text(), mesh_shape,
        partition_axes=topo.partition_axes,
        replication_axes=topo.replication_axes)
    # model-vs-census cross-check: the analytical per-stage wire bytes of
    # the ACTIVE policy against the measured census (upcast=True because
    # the dry-run compiles for host devices, where XLA widens bf16
    # collectives to f32 on the wire).
    predicted = predict_traffic(
        model, topo, engine.gather_policy, engine.sync_policy,
        micro_steps=record["micro_steps"],
        mode="train" if spec["kind"] == "train" else "serve",
        upcast_float_collectives=True)
    record["autotune_cross_check"] = compare_census(
        predicted["by_stage"], record["stats"]["by_stage"])
    # boundary cross-check: the compiled step must show hop-2 at the plan's
    # bucket granularity (measured census vs the static plan).
    if "boundary" in record:
        measured_b = record["stats"]["boundary"]
        record["boundary"]["measured"] = measured_b
        record["boundary"]["bucket_count_match"] = (
            topo.replication_degree == 1
            or measured_b["hop2_ops"]
            == record["boundary"]["n_hop2_collectives"])
    record["total_s"] = round(time.time() - t0, 1)

    out_dir.mkdir(parents=True, exist_ok=True)
    stem = f"{cfg.name}__{shape}__{record['mesh']}" + (f"__{tag}" if tag else "")
    (out_dir / f"{stem}.json").write_text(json.dumps(record, indent=1))
    return record


def main():
    global TRAIN_MICRO_STEPS
    # The production meshes need 512 virtual CPU devices; set before JAX
    # first touches a backend, and only from the entry point, so importing
    # this module leaves the device set alone.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--policy", choices=["manual", "auto"], default="manual",
                    help="'auto' = rank GatherPolicy/SyncPolicy candidates "
                         "over --link-profile (core/autotune.py), print the "
                         "ranked table and run the winner; 'manual' = use "
                         "the flags below verbatim")
    ap.add_argument("--link-profile", default="v5e",
                    help="link-bandwidth table for --policy auto: v5e, "
                         "efa-100g, efa-400g, or a registered custom "
                         "profile (core/linkmodel.py)")
    ap.add_argument("--hierarchical", type=int, default=1,
                    help="1 = staged hierarchical gathers (GatherPolicy "
                         "topology from --gather-order), 0 = one flat "
                         "collective over the partition group")
    ap.add_argument("--gather-order", default="inner_first",
                    choices=["inner_first", "outer_first"],
                    help="staged-gather order: inner_first = reorder-free "
                         "2-stage, outer_first = paper-faithful 3-stage")
    ap.add_argument("--sync-mode", default="2hop",
                    choices=["2hop", "allreduce_slice"],
                    help="SyncPolicy: 2-hop gradient sync vs the Fig-14 "
                         "all-reduce+slice ablation")
    ap.add_argument("--partition-size", type=int, default=0)
    ap.add_argument("--zero3", action="store_true")
    ap.add_argument("--bf16-scores", action="store_true")
    ap.add_argument("--quant-gather", action="store_true",
                    help="int8 block-quantized wire/serving-weight gathers "
                         "(GatherPolicy wire_dtype='int8'; under --policy "
                         "auto this *permits* rather than forces int8)")
    ap.add_argument("--hop1-wire-dtype", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="hop-1 gradient reduce-scatter wire: fp32 = exact "
                         "staged adjoint, int8 = ZeRO++-qgZ per-stage "
                         "block-quantized reduce-scatter (fp32 inter-stage "
                         "accumulation; under --policy auto this permits "
                         "rather than forces the int8 hop-1)")
    ap.add_argument("--compress-hop2", default="off",
                    choices=["off", "bf16", "int8"],
                    help="hop-2 replication-group all-reduce wire: bf16 "
                         "cast or the int8 quantized decompress leg "
                         "(core/schedule.py); 'off' = fp32")
    ap.add_argument("--prefetch", type=int, default=1,
                    help="1 = double-buffered lookahead gathers (layer i+1 "
                         "gathered during layer i's compute; the default), "
                         "0 = serial reference schedule")
    ap.add_argument("--clip-mode", default="exact",
                    choices=["exact", "approx"],
                    help="boundary clip: 'exact' = barriered global-norm "
                         "reference, 'approx' = bucket k's AdamW pipelined "
                         "under bucket k+1's hop-2 with a one-bucket-stale "
                         "clip factor (bucketed schedule only; under "
                         "--policy auto this permits rather than forces)")
    ap.add_argument("--hbm-budget-gb", type=float, default=0,
                    help="per-device HBM budget in GiB for the memory "
                         "planner (core/memplan.py): picks the minimal "
                         "partition group that fits (paper §3.1) when no "
                         "--partition-size is pinned, gates --policy auto "
                         "candidates, and reports plan-vs-compiled "
                         "footprints per cell; 0 = no budget")
    ap.add_argument("--boundary-schedule", default="bucketed",
                    choices=["serial", "bucketed"],
                    help="gradient-accumulation boundary: 'bucketed' "
                         "software-pipelines hop-2 buckets against the "
                         "norm/decompress compute (core/schedule.py), "
                         "'serial' is the monolithic reference")
    ap.add_argument("--hop2-bucket-mb", type=float, default=32.0,
                    help="fixed-byte bucket size of the hop-2 pipeline "
                         "(fp32 gradient megabytes; under --policy auto "
                         "the tuner ranks this axis itself)")
    ap.add_argument("--mlstm-chunk", type=int, default=0)
    ap.add_argument("--tp", type=int, default=0)
    ap.add_argument("--serve-footprint", action="store_true",
                    help="pick p from the inference memory footprint")
    ap.add_argument("--micro-steps", type=int, default=TRAIN_MICRO_STEPS)
    args = ap.parse_args()
    TRAIN_MICRO_STEPS = args.micro_steps

    mcfg = MiCSConfig(
        micro_steps=TRAIN_MICRO_STEPS,
        hierarchical=bool(args.hierarchical),
        gather_order=args.gather_order,
        sync_mode=args.sync_mode,
        scores_bf16=args.bf16_scores,
        mlstm_chunk=args.mlstm_chunk,
        quant_gather=args.quant_gather,
        hop1_wire_dtype=args.hop1_wire_dtype,
        compress_hop2=(False if args.compress_hop2 == "off"
                       else args.compress_hop2),
        prefetch=bool(args.prefetch),
        clip_mode=args.clip_mode,
        policy=args.policy,
        link_profile=args.link_profile,
        boundary_schedule=args.boundary_schedule,
        hop2_bucket_mb=args.hop2_bucket_mb,
        hbm_budget_gb=args.hbm_budget_gb or None,
    )

    todo = []
    if args.all:
        for cfg, shape, spec, skip in cells():
            todo.append((cfg.name, shape))
    else:
        todo.append((args.arch, args.shape))

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = 0
    for arch, shape in todo:
        for multi in meshes:
            label = f"{arch} x {shape} x {'multi' if multi else 'single'}"
            try:
                rec = run_cell(arch, shape, multi, mcfg, tag=args.tag,
                               partition_size=args.partition_size or None,
                               zero3=args.zero3, tp=args.tp or None,
                               serve_footprint=args.serve_footprint)
                pf = rec["stats"]["prefetch"]
                msg = (f"OK   {label}: compile={rec['compile_s']}s "
                       f"flops={rec['stats']['dot_flops']:.3e} "
                       f"wire={rec['stats']['total_wire_bytes']:.3e}B "
                       f"carried_gathers={pf['carried_all_gathers']}")
                mp = rec.get("memplan", {})
                if mp:
                    msg += f" mem={mp['total_gib']:.2f}GiB"
                    if mp.get("plan_vs_compiled_ratio"):
                        msg += (" (plan/compiled="
                                f"{mp['plan_vs_compiled_ratio']:.2f})")
                if "boundary" in rec:
                    bd, pr = rec["boundary"], rec["boundary"]["predicted"]
                    msg += (f" hop2[{bd['mode']}x{bd['n_hop2_collectives']}]="
                            f"{pr['t_exposed_s']*1e6:.0f}us exposed"
                            f"/{pr['t_total_s']*1e6:.0f}us total"
                            f" interleaved="
                            f"{bd['measured']['interleaved']}")
                print(msg, flush=True)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"FAIL {label}: {type(e).__name__}: {str(e)[:400]}",
                      flush=True)
                traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
