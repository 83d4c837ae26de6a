"""Serial vs double-buffered-prefetch gather schedules on the host mesh,
plus the autotuner's predicted-vs-measured ledger per gather policy and the
boundary scheduler's serial-vs-bucketed hop-2 ledger.

Run standalone (benchmarks/run.py invokes it as a subprocess so the main
benchmark process keeps its single CPU device):

  PYTHONPATH=src python benchmarks/comm_bench.py [--smoke] [--steps N]
      [--warmup N] [--check]

``--smoke`` runs the CI-sized variant (fewer timing steps, same coverage).
Prints one JSON object (saved as BENCH_comm.json by run.py):

* per-schedule wall time per training step, the HLO-census
  gathered-bytes/collective counts, the carried-gather prefetch evidence,
  and the loss trajectories (which must be bitwise equal — the schedules
  differ only in *when* gathers are issued, never in values);
* a ``policies`` section: for each gather/sync policy (flat / inner_first /
  outer_first bf16 wire, inner_first int8, and the qgZ rows shipping the
  int8 block-quantized hop-1 gradient wire), the analytical per-stage wire
  bytes (core/autotune.predict_traffic) against the measured census of the
  compiled step, the α-β modeled comm time under two link profiles (v5e +
  efa-100g, core/linkmodel.py), a measured wall time, and the
  ``fit_inputs`` stage ledger that ``tools/fit_profile.py`` fits per-tier
  (α, β) from;
* a ``boundary`` section on a replicated mesh (hop 2 live): serial vs
  bucketed-exact vs bucketed-approx (``clip_mode='approx'``: AdamW
  pipelined under the next bucket's hop-2 with a one-bucket-stale clip
  factor) vs host-offloaded (``carry_offload='host'`` +
  ``offload_opt=True``) boundary cells — exact/offload trajectories
  bitwise equal, approx within ``APPROX_CLIP_LOSS_RTOL``, per-cell
  measured wall times, the bucket-granular hop-2 census, and an
  ``overlap`` roll-up of measured step time vs the link model's predicted
  exposed-hop-2 time per cell and profile;
* a ``cells`` section in the shared perf-matrix schema
  (repro.bench.measure): every timed cell carries its declarative config
  + config hash, the timing samples with median/MAD/IQR variance, and
  its local contract verdict;
* the autotuner's full ranked table per profile (``autotune_rankings``).

This script is the ``comm`` suite of the declarative perf matrix
(``benchmarks/matrix.py``); ``--check`` is a thin shim that applies
exactly the gates ``repro.bench.matrixdef`` declares for this suite —
bitwise/census/rtol contracts per cell, and the variance-aware step-time
regression gates of the non-serial boundary cells against the same-run
serial reference (the host-offload cell gets a wider threshold for its
documented CPU io_callback overhead).
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench import measure as MS
from repro.bench.matrixdef import COMM_BOUNDARY_CELLS, COMM_POLICY_LABELS
from repro.configs import get_config, smoke_variant
from repro.core.autotune import (
    compare_census, cost_candidate, cost_hop2_schedule, predict_traffic,
    rank_policies,
)
from repro.core import memplan
from repro.core.comm import CommEngine
from repro.core.hostoffload import stash_clear
from repro.core.linkmodel import get_profile
from repro.core.mics import (
    MiCSConfig, build_train_step, init_state, init_state_shapes,
    make_batch_shapes,
)
from repro.core.schedule import APPROX_CLIP_LOSS_RTOL, plan_boundary
from repro.core.topology import MiCSTopology, make_host_mesh
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.roofline.hlo_stats import analyze

STEPS = 8
WARMUP = 1  # timed loops discard this many post-compile steps
MICRO = 2
BOUNDARY_BUCKET_MB = 0.05  # small enough to split the smoke model's pools

PROFILES = ("v5e", "efa-100g")
# (label, MiCSConfig fields) — >= 3 policies for the predicted-vs-measured
# ledger (acceptance criterion of ISSUE 2); the GatherPolicy/SyncPolicy are
# derived via CommEngine.from_config so the ledger prices exactly what the
# step runs.  The qgZ rows ship the int8 hop-1 gradient wire (ISSUE 4);
# the +host row streams the prefetch carry over the host tier, giving
# tools/fit_profile.py a ``tier='host'`` stage to constrain (α, β) from.
# Labels are pinned by repro.bench.matrixdef.COMM_POLICY_LABELS — the
# declared matrix cells — so coverage drift fails the matrix loudly.
POLICIES = tuple(zip(COMM_POLICY_LABELS, (
    dict(hierarchical=False),
    dict(),
    dict(gather_order="outer_first"),
    dict(quant_gather=True),
    dict(hop1_wire_dtype="int8"),
    dict(quant_gather=True, hop1_wire_dtype="int8"),
    dict(prefetch=True, carry_offload="host"),
    # second host row at a different bytes-per-event ratio (fp32 carry is
    # 2x the bytes of bf16 at the same event count) — separates the host
    # α from its β in the fit
    dict(prefetch=True, gather_dtype="float32", carry_offload="host"),
)))

# Boundary cells (replicated mesh): the bitwise-exact schedules, the
# approximate-clip pipeline, and the host-offloaded cell (carry + AdamW
# moments streamed through the host stash; numerics still bitwise-exact).
# Cell labels pinned by matrixdef.COMM_BOUNDARY_CELLS, thresholds by
# matrixdef.COMM_BOUNDARY_THRESHOLDS.
BOUNDARY_CELLS = tuple(zip(COMM_BOUNDARY_CELLS, (
    dict(boundary_schedule="serial"),
    dict(boundary_schedule="bucketed"),
    dict(boundary_schedule="bucketed", clip_mode="approx"),
    dict(boundary_schedule="bucketed", carry_offload="host",
         offload_opt=True),
)))


def _timed_steps(step, state, batch, steps, warmup):
    """Run ``warmup + steps`` training steps; per-step wall times (each
    blocked on the loss, so the samples are honest) + the timed-loop loss
    trajectory."""
    m = None
    for _ in range(warmup):
        state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
    samples, traj = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, m = step(state, batch)
        traj.append((float(m["loss"]), float(m["grad_norm"])))
        samples.append(time.perf_counter() - t0)
    return state, MS.TimingStats(tuple(samples), warmup=warmup), traj


def run(steps: int = STEPS, warmup: int = WARMUP) -> dict:
    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh = make_host_mesh(1, 1, 4, 2)  # p=4 partition group, tp=2
    topo = MiCSTopology(mesh)
    model = build_model(cfg, tp=2)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))

    rng = np.random.default_rng(5)
    b, t = 8, 32
    batch = {
        "tokens": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                            jnp.int32),
        "targets": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                             jnp.int32),
        "mask": jnp.ones((MICRO, b, t), jnp.float32),
    }

    def cell_config(section, label, **extra):
        return dict(suite="comm", section=section, cell=label,
                    mesh=mesh_shape, model=cfg.name, micro_steps=MICRO,
                    batch=[b, t], steps=steps, warmup=warmup, **extra)

    cells = {}
    out = {"mesh": mesh_shape, "partition_size": topo.partition_size,
           "steps": steps, "warmup": warmup, "micro_steps": MICRO}
    for label, prefetch in (("serial", False), ("prefetch", True)):
        mcfg = MiCSConfig(micro_steps=MICRO, prefetch=prefetch)
        step = build_train_step(model, topo, mcfg,
                                OptConfig(total_steps=100, warmup_steps=0,
                                          lr_max=3e-3))
        stats = analyze(
            step.lower(init_state_shapes(model),
                       make_batch_shapes(model, MICRO * b, t, MICRO))
                .compile().as_text(),
            mesh_shape,
            partition_axes=topo.partition_axes,
            replication_axes=topo.replication_axes)
        gather_stages = {k: v for k, v in stats["by_stage"].items()
                         if k.startswith("param_gather")}

        state = init_state(model, topo, seed=11)
        _state, timing, traj = _timed_steps(step, state, batch, steps,
                                            warmup)
        out[label] = {
            "us_per_step": round(timing.median_s * 1e6, 1),
            "gathered_wire_bytes": sum(
                v["wire_bytes"] for v in gather_stages.values()),
            "param_gather_count": sum(
                v["count"] for v in gather_stages.values()),
            "carried_all_gathers": stats["prefetch"]["carried_all_gathers"],
            "total_wire_bytes": stats["total_wire_bytes"],
            "losses": [loss for loss, _gn in traj],
        }
        cells[f"comm/gather/{label}"] = MS.timing_cell(
            cell_config("gather", label, schedule=label), timing,
            metrics={
                "gathered_wire_bytes": out[label]["gathered_wire_bytes"],
                "total_wire_bytes": out[label]["total_wire_bytes"],
                "carried_all_gathers": out[label]["carried_all_gathers"],
            })
    out["loss_bitwise_equal"] = out["serial"]["losses"] \
        == out["prefetch"]["losses"]
    cells["comm/gather/prefetch"]["ok"] = out["loss_bitwise_equal"]
    if not out["loss_bitwise_equal"]:
        cells["comm/gather/prefetch"]["detail"] = "prefetch changed the loss"
    out["speedup"] = round(
        out["serial"]["us_per_step"] / out["prefetch"]["us_per_step"], 3)
    out["policies"] = policy_ledger(model, topo, mesh_shape, batch, steps,
                                    warmup, cells, cell_config)
    out["boundary"] = boundary_bench(cfg, steps, warmup, cells)
    out["autotune_rankings"] = {
        name: rank_policies(model, topo, name, micro_steps=MICRO,
                            prefetch=True).describe()
        for name in PROFILES
    }
    out["cells"] = cells
    return out


def policy_ledger(model, topo, mesh_shape, batch, steps, warmup, cells,
                  cell_config) -> dict:
    """Predicted-vs-measured per gather policy, on two link profiles.

    Measured: per-stage census wire bytes of the compiled (serial) train
    step, plus its wall time per step.  Predicted:
    core/autotune.predict_traffic with ``upcast_float_collectives=True``
    (the census is compiled for host CPUs, where XLA widens bf16
    collectives to f32).  Modeled times use the un-upcast traffic — the
    real wire cost on each profile.  ``fit_inputs`` is the per-stage
    (tier, α-events, wire bytes) ledger plus the measured time —
    exactly what ``tools/fit_profile.py`` least-squares a per-tier (α, β)
    table from on real hardware.
    """
    ledger = {}
    for label, mcfg_kw in POLICIES:
        kw = dict(prefetch=False)
        kw.update(mcfg_kw)
        mcfg = MiCSConfig(micro_steps=MICRO, **kw)
        engine = CommEngine.from_config(topo, mcfg)
        step = build_train_step(model, topo, mcfg,
                                OptConfig(total_steps=100, warmup_steps=0,
                                          lr_max=3e-3))
        stats = analyze(
            step.lower(init_state_shapes(model),
                       make_batch_shapes(model, MICRO * 8, 32, MICRO))
                .compile().as_text(),
            mesh_shape,
            partition_axes=topo.partition_axes,
            replication_axes=topo.replication_axes)
        state = init_state(model, topo, seed=11)
        _state, timing, _traj = _timed_steps(step, state, batch, steps,
                                             warmup)
        t_measured = timing.median_s
        gp, sp = engine.gather_policy, engine.sync_policy
        predicted = predict_traffic(model, topo, gp, sp, micro_steps=MICRO,
                                    upcast_float_collectives=True)
        cmp = compare_census(predicted["by_stage"], stats["by_stage"])
        wire_pred = predict_traffic(model, topo, gp, sp, micro_steps=MICRO,
                                    profile=get_profile("v5e"))
        byte_match = all(
            abs(row["ratio"] - 1.0) <= 0.02 for row in cmp.values())
        entry = {
            "predicted_vs_measured": cmp,
            "byte_match": byte_match,
            "measured_total_wire_bytes": stats["total_wire_bytes"],
            "measured_us_per_step": round(t_measured * 1e6, 1),
            "modeled_t_comm_us": {},
            "fit_inputs": {
                "t_measured_s": t_measured,
                "stages": {
                    lbl: {
                        "tier": e["tier"],
                        # one (g-1)-hop ring per collective launch (count ==
                        # events for float wires; int8 ships q + scales, so
                        # its launches — and alpha events — double)
                        "alpha_events": (
                            e["events"] * 2 * (e["group_size"] - 1)
                            if lbl == "hop2"
                            else e["count"] * (e["group_size"] - 1)),
                        "wire_bytes": e["wire_bytes"],
                    }
                    for lbl, e in wire_pred["by_stage"].items()
                },
            },
        }
        if gp.carry_offload == "host":
            # The carry's d2h/h2d stream, ledgered exactly as
            # cost_candidate's ``host_offload`` stage prices it: 2 x stack
            # x flat_len bytes per scanned pool per micro-step over the
            # host tier, one α-event per transfer (point-to-point — no
            # ring, so no (g-1) hop factor).
            cb = memplan._COMPUTE_BYTES[gp.wire_dtype]
            scanned = {pl.name for pl in model.pools}
            host_bytes, host_events = 0.0, 0
            for name, (stack, _tp, flat_len) in \
                    model.global_flat_shapes().items():
                if name in scanned and stack > 1:
                    host_bytes += 2.0 * MICRO * stack * flat_len * cb
                    host_events += 2 * MICRO * stack
            entry["fit_inputs"]["stages"]["carry_offload"] = {
                "tier": "host", "alpha_events": host_events,
                "wire_bytes": host_bytes}
            stash_clear()
        for name in PROFILES:
            cand = cost_candidate(model, topo, get_profile(name), gp, sp,
                                  micro_steps=MICRO)
            entry["modeled_t_comm_us"][name] = round(cand.t_comm_s * 1e6, 2)
        ledger[label] = entry
        worst = max(abs(row["ratio"] - 1.0) for row in cmp.values()) \
            if cmp else 0.0
        cells[f"comm/policy/{label}"] = MS.timing_cell(
            cell_config("policy", label, policy=mcfg_kw), timing,
            metrics={
                "measured_total_wire_bytes": stats["total_wire_bytes"],
                "pvm_worst_abs_ratio_err": worst,
                "modeled_t_comm_us": entry["modeled_t_comm_us"],
            },
            ok=byte_match,
            detail=None if byte_match else "census byte mismatch")
    return ledger


def boundary_bench(cfg, steps, warmup, cells) -> dict:
    """The ``BOUNDARY_CELLS`` grid on a replicated mesh (repl=2, p=2, tp=2
    — hop 2 is live).  serial / bucketed / bucketed_offload must produce
    bitwise equal loss/grad-norm trajectories (the offload cell merely
    relocates the carry + AdamW moments to the host stash);
    bucketed_approx pipelines AdamW under hop-2 with a one-bucket-stale
    clip factor, so its trajectory may drift — bounded by
    ``APPROX_CLIP_LOSS_RTOL`` on the final loss.  The ledger records
    per-cell timing stats (median + MAD over the timed steps), the
    bucket-granular hop-2 census, and an ``overlap`` roll-up against the
    link model's exposed-hop-2 prediction per profile; the step-time
    regression gates themselves live in the matrix (variance-aware, vs
    the same-run serial reference)."""
    mesh = make_host_mesh(1, 2, 2, 2)
    topo = MiCSTopology(mesh)
    model = build_model(cfg, tp=2)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    rng = np.random.default_rng(17)
    b, t = 8, 32
    batch = {
        "tokens": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                            jnp.int32),
        "targets": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                             jnp.int32),
        "mask": jnp.ones((MICRO, b, t), jnp.float32),
    }
    bplan = plan_boundary(model, topo, mode="bucketed",
                          bucket_mb=BOUNDARY_BUCKET_MB)
    out = {"mesh": mesh_shape, "bucket_mb": BOUNDARY_BUCKET_MB,
           "n_buckets": bplan.n_buckets, "steps": steps}

    def cell_config(section, label, **extra):
        return dict(suite="comm", section=section, cell=label,
                    mesh=mesh_shape, model=cfg.name, micro_steps=MICRO,
                    batch=[b, t], steps=steps, warmup=warmup, **extra)
    timings = {}
    for label, cell_kw in BOUNDARY_CELLS:
        mcfg = MiCSConfig(micro_steps=MICRO,
                          hop2_bucket_mb=BOUNDARY_BUCKET_MB, **cell_kw)
        step = build_train_step(model, topo, mcfg,
                                OptConfig(total_steps=100, warmup_steps=0,
                                          lr_max=3e-3))
        stats = analyze(
            step.lower(init_state_shapes(model,
                                         offload_opt=mcfg.offload_opt),
                       make_batch_shapes(model, MICRO * b, t, MICRO))
                .compile().as_text(),
            mesh_shape,
            partition_axes=topo.partition_axes,
            replication_axes=topo.replication_axes)
        state = init_state(model, topo, seed=13,
                           offload_opt=mcfg.offload_opt)
        _state, timing, traj = _timed_steps(step, state, batch, steps,
                                            warmup)
        timings[label] = timing
        out[label] = {
            "us_per_step": round(timing.median_s * 1e6, 1),
            "us_per_step_min": round(timing.min_s * 1e6, 1),
            "trajectory": traj,
            "census_boundary": stats["boundary"],
        }
        if mcfg.offload_opt or mcfg.carry_offload == "host":
            stash_clear()
    out["trajectory_bitwise_equal"] = (
        out["serial"]["trajectory"] == out["bucketed"]["trajectory"])
    out["offload_bitwise_equal"] = (
        out["bucketed"]["trajectory"] == out["bucketed_offload"]["trajectory"])
    exact_final = out["bucketed"]["trajectory"][-1][0]
    approx_final = out["bucketed_approx"]["trajectory"][-1][0]
    out["approx_final_loss_rtol"] = abs(approx_final - exact_final) \
        / abs(exact_final)
    out["measured_exposed_delta_us"] = round(
        out["serial"]["us_per_step"] - out["bucketed"]["us_per_step"], 1)
    sync = CommEngine.from_config(
        topo, MiCSConfig(boundary_schedule="bucketed")).sync_policy
    out["predicted"] = {
        name: {
            "serial": cost_hop2_schedule(
                model, topo, get_profile(name), sync, boundary="serial"),
            "bucketed": cost_hop2_schedule(
                model, topo, get_profile(name), sync, boundary="bucketed",
                bucket_mb=BOUNDARY_BUCKET_MB),
            "bucketed_approx": cost_hop2_schedule(
                model, topo, get_profile(name), sync, boundary="bucketed",
                bucket_mb=BOUNDARY_BUCKET_MB, clip_mode="approx"),
        }
        for name in PROFILES
    }
    # The overlap roll-up: measured step time per cell against the link
    # model's exposed-hop-2 prediction.  The offload cell runs the exact
    # bucketed schedule — its hop-2 prediction is the bucketed row (the
    # host stream is priced separately, cost_candidate's host_offload
    # stage).
    pred_key = {"serial": "serial", "bucketed": "bucketed",
                "bucketed_approx": "bucketed_approx",
                "bucketed_offload": "bucketed"}
    out["overlap"] = {
        label: {
            "us_per_step": out[label]["us_per_step"],
            "us_per_step_min": out[label]["us_per_step_min"],
            "vs_serial": round(out[label]["us_per_step_min"]
                               / out["serial"]["us_per_step_min"], 3),
            "predicted_exposed_hop2_us": {
                name: round(
                    out["predicted"][name][pred_key[label]]["t_exposed_s"]
                    * 1e6, 2)
                for name in PROFILES},
        }
        for label, _ in BOUNDARY_CELLS
    }

    # per-cell contract verdicts (the matrix's contract gates read these)
    def census_ok(label):
        census = out[label]["census_boundary"]
        return census["interleaved"] and census["hop2_ops"] == out["n_buckets"]

    verdicts = {
        "serial": (True, None),
        "bucketed": (
            out["trajectory_bitwise_equal"] and census_ok("bucketed"),
            "bucketed boundary changed numerics or census off-granular"),
        "bucketed_approx": (
            census_ok("bucketed_approx")
            and all(np.isfinite(v)
                    for pair in out["bucketed_approx"]["trajectory"]
                    for v in pair)
            and out["approx_final_loss_rtol"] <= APPROX_CLIP_LOSS_RTOL,
            f"approx clip diverged "
            f"(rtol={out['approx_final_loss_rtol']:.4f})"),
        "bucketed_offload": (
            out["offload_bitwise_equal"] and census_ok("bucketed_offload"),
            "host offload changed numerics or census off-granular"),
    }
    for label, _ in BOUNDARY_CELLS:
        ok, why = verdicts[label]
        cells[f"comm/boundary/{label}"] = MS.timing_cell(
            cell_config("boundary", label, schedule=label,
                        bucket_mb=BOUNDARY_BUCKET_MB,
                        n_buckets=out["n_buckets"]),
            timings[label],
            metrics={
                "hop2_ops": out[label]["census_boundary"]["hop2_ops"],
                "predicted_exposed_hop2_us":
                    out["overlap"][label]["predicted_exposed_hop2_us"],
            },
            ok=ok, detail=None if ok else why)

    # serial keeps a coarse hop-2 (strictly fewer ops than the bucket
    # plan) and the model's exposed-time ordering holds per profile
    pred_ok = out["serial"]["census_boundary"]["hop2_ops"] < out["n_buckets"]
    for name, pred in out["predicted"].items():
        pred_ok &= pred["serial"]["t_exposed_s"] == pred["serial"]["t_total_s"]
        pred_ok &= pred["bucketed"]["t_exposed_s"] \
            <= pred["bucketed"]["t_total_s"]
        pred_ok &= pred["bucketed_approx"]["t_exposed_s"] \
            <= pred["bucketed"]["t_exposed_s"] + 1e-12
    cells["comm/contract/predicted_exposed"] = MS.contract_cell(
        cell_config("contract", "predicted_exposed"), pred_ok,
        detail=None if pred_ok else "exposed-hop2 prediction ordering broke")
    return out


def finish_cells(out: dict) -> None:
    """Post-run contract cells that span sections."""
    host_ok = any(
        s["tier"] == "host"
        for entry in out["policies"].values()
        for s in entry["fit_inputs"]["stages"].values())
    fit_ok = host_ok and all(
        entry["fit_inputs"]["t_measured_s"] > 0
        and entry["fit_inputs"]["stages"]
        for entry in out["policies"].values())
    out["cells"]["comm/contract/host_fit_stage"] = MS.contract_cell(
        dict(suite="comm", section="contract", cell="host_fit_stage"),
        fit_ok,
        detail=None if fit_ok else
        "no host-tier fit stage — tools/fit_profile.py host fit unexercised")


def check_ledger(out: dict, smoke: bool) -> None:
    """The standalone gate shim: apply exactly the matrix's declared gates
    for the ``comm`` suite (contract + variance-aware step-time ratios)."""
    from repro.bench.runner import check_suite

    failures = check_suite("comm", out, smoke=smoke)
    if failures:
        print("comm bench gate FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        sys.exit(1)


def main() -> None:
    # Virtual CPU devices for the host mesh; set before JAX first touches
    # a backend, and only when run as a script, so importing this module
    # leaves the device set alone.
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: fewer timing steps, same coverage")
    ap.add_argument("--steps", type=int, default=0,
                    help="timing steps per schedule (default 8, smoke 5)")
    ap.add_argument("--warmup", type=int, default=WARMUP,
                    help="post-compile steps discarded before timing")
    ap.add_argument("--check", action="store_true",
                    help="apply the matrix's comm-suite gates after "
                         "printing the JSON")
    args = ap.parse_args()
    steps = args.steps or (5 if args.smoke else STEPS)
    out = run(steps, args.warmup)
    finish_cells(out)
    print(json.dumps(out, indent=1))
    if args.check:
        check_ledger(out, args.smoke)


if __name__ == "__main__":
    main()
