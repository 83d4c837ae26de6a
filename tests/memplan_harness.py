"""Memory-planner verification harness, run in a subprocess with 8 virtual
CPU devices (same pattern as autotune_harness.py).  Prints one JSON object
with named check results; tests/test_memplan.py asserts on them, and the CI
``bench`` job runs it with ``--check`` as the memplan smoke gate (the JSON
is the uploaded ledger artifact).

The property under test is the tentpole contract of core/memplan.py: the
*analytical* per-device HBM footprint (``predict_footprint``) matches XLA's
own compiled ``memory_analysis()`` of the actually-built train step — the
same predicted-vs-compiled discipline the autotuner applies to wire bytes.
Argument bytes (the donated fp32 state + batch) must match EXACTLY;
transient bytes within the documented ``memplan.MEM_RTOL``.

Checks:

  footprint_match       3 gather topologies x {stored, remat} prefetch
                        carries + the serial schedule + the qgZ hop-1 wire
                        on the p=4/repl=2 topology: args exact, temp within
                        tolerance (the stored carry, which training takes
                        only for enc-dec decoder pools, is forced here by
                        harness_util.stored_carry)
  footprint_degenerate  partition group == world (p=8, no replication → no
                        hop-2 staging) and a single-device mesh (p=1,
                        nothing on the wire): same contract
  remat_lowers_peak     the remat carry (the training default) measurably
                        lowers the COMPILED temp bytes vs the stored carry
                        while 3-step loss/grad-norm trajectories stay
                        bitwise equal
  census_match_remat    the remat schedule's collective event counts
                        (2·s·stack+1 gathers, s·stack adjoints) are
                        instruction-exact against the measured census
  carried_buffer_census the carried-gather bytes are visible to
                        hlo_stats.prefetch_census under BOTH carries —
                        remat keeps the double-buffered forward (the
                        residual it drops is what remat_lowers_peak
                        measures)
  offload_lowers_peak   carry_offload='host' lowers BOTH the predicted and
                        the XLA-compiled temp bytes vs the stored carry,
                        and offload_opt=True additionally shrinks the
                        donated argument bytes (the m/v shards leave HBM)
                        — with the predicted-vs-compiled contract (args
                        exact, temp within MEM_RTOL) holding on every
                        offload cell
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import contextlib
import json
import sys

import jax.numpy as jnp
import numpy as np

from harness_util import stored_carry
from repro.bench import measure as MS
from repro.configs import get_config, smoke_variant
from repro.core import memplan as M
from repro.core.autotune import compare_census, predict_traffic
from repro.core.comm import policies_from_config
from repro.core.mics import (
    MiCSConfig, build_train_step, init_state, init_state_shapes,
    make_batch_shapes,
)
from repro.core.topology import MiCSTopology, make_host_mesh
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.roofline.hlo_stats import analyze

RESULTS = {}
MICRO = 2
GLOBAL_BATCH = 16
SEQ = 16

check = MS.make_check(RESULTS)


def _build(mesh_dims, part, repl, **mcfg_kw):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    topo = MiCSTopology(make_host_mesh(*mesh_dims),
                        partition_axes=part, replication_axes=repl)
    model = build_model(cfg, tp=1)
    mcfg = MiCSConfig(micro_steps=MICRO, **mcfg_kw)
    step = build_train_step(model, topo, mcfg, OptConfig(
        total_steps=100, warmup_steps=0, lr_max=3e-3))
    return model, topo, mcfg, step


def _compile(model, step, offload_opt=False):
    return step.lower(
        init_state_shapes(model, offload_opt=offload_opt),
        make_batch_shapes(model, GLOBAL_BATCH, SEQ, MICRO),
    ).compile()


def _carry(carry: str):
    """The context a ``carry`` cell runs in: ``'stored'`` forces the stored
    carry, ``'remat'`` is the default."""
    return stored_carry() if carry == "stored" else contextlib.nullcontext()


def _footprint_cell(tag, mesh_dims, part, repl, **mcfg_kw):
    """One predicted-vs-compiled cell; returns its ledger row."""
    model, topo, mcfg, step = _build(mesh_dims, part, repl, **mcfg_kw)
    compiled = _compile(model, step, offload_opt=mcfg.offload_opt)
    ma = compiled.memory_analysis()
    gp, sp = policies_from_config(mcfg)
    n_dev = int(np.prod(mesh_dims))
    local_batch = (GLOBAL_BATCH // MICRO) // n_dev  # tp=1: all devices data
    plan = M.predict_footprint(
        model, topo, gp, sp, micro_steps=MICRO, mode="train",
        local_batch=local_batch, seq=SEQ, boundary=mcfg.boundary_schedule,
        hop2_bucket_mb=mcfg.hop2_bucket_mb, offload_opt=mcfg.offload_opt)
    args_m = ma.argument_size_in_bytes
    temp_m = ma.temp_size_in_bytes
    row = {
        "predicted_args_bytes": plan.args_bytes,
        "measured_args_bytes": args_m,
        "predicted_temp_bytes": plan.temp_bytes,
        "measured_temp_bytes": temp_m,
        "temp_ratio": plan.temp_bytes / temp_m,
        "components": dict(plan.components),
    }
    assert plan.args_bytes == args_m, \
        f"{tag}: predicted args {plan.args_bytes} != measured {args_m}"
    assert abs(plan.temp_bytes - temp_m) <= M.MEM_RTOL * temp_m, \
        f"{tag}: temp predicted {plan.temp_bytes} vs measured {temp_m} " \
        f"outside rtol {M.MEM_RTOL}"
    return row


BASE = ((1, 2, 4, 1), ("shard",), ("pod", "repl"))


# ---------------------------------------------------------------------------
@check("footprint_match")
def _footprint_match():
    detail = {}
    for topology, kw in (
        ("flat", dict(hierarchical=False)),
        ("inner_first", dict()),
        ("outer_first", dict(gather_order="outer_first")),
    ):
        for carry in ("stored", "remat"):
            tag = f"{topology}/{carry}"
            with _carry(carry):
                detail[tag] = _footprint_cell(tag, *BASE, **kw)
    detail["inner_first/serial"] = _footprint_cell(
        "inner_first/serial", *BASE, prefetch=False)
    detail["inner_first/qgz"] = _footprint_cell(
        "inner_first/qgz", *BASE, hop1_wire_dtype="int8")
    RESULTS["footprint_match_detail"] = detail


# ---------------------------------------------------------------------------
@check("footprint_degenerate")
def _footprint_degenerate():
    detail = {
        # partition group == world: no replication, hop 2 vanishes
        "world_partition": _footprint_cell(
            "world_partition", (1, 1, 8, 1), ("shard",), ("repl",)),
        # single-device mesh: p = 1, nothing on the wire
        "single_device": _footprint_cell(
            "single_device", (1, 1, 1, 1), ("shard",), ("repl",)),
    }
    assert "hop2_staging" not in detail["world_partition"]["components"]
    RESULTS["footprint_degenerate_detail"] = detail


# ---------------------------------------------------------------------------
@check("remat_lowers_peak")
def _remat_lowers_peak():
    rng = np.random.default_rng(3)
    cfg = smoke_variant(get_config("llama3.2-1b"))
    b, t = 8, 16
    batch = {
        "tokens": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                            jnp.int32),
        "targets": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                             jnp.int32),
        "mask": jnp.ones((MICRO, b, t), jnp.float32),
    }
    temp = {}
    traj = {}
    for carry in ("stored", "remat"):
        with _carry(carry):
            model, topo, _mcfg, step = _build(*BASE)
            temp[carry] = _compile(model, step).memory_analysis() \
                .temp_size_in_bytes
            state = init_state(model, topo, seed=7)
            rows = []
            for _ in range(3):
                state, m = step(state, batch)
                rows.append((float(m["loss"]), float(m["grad_norm"])))
        traj[carry] = rows
    assert traj["stored"] == traj["remat"], \
        f"remat changed the numerics: {traj}"
    assert temp["remat"] < temp["stored"], temp
    RESULTS["remat_lowers_peak_detail"] = {
        "temp_bytes": temp,
        "saving_bytes": temp["stored"] - temp["remat"],
        "trajectory_bitwise_equal": True,
    }


# ---------------------------------------------------------------------------
@check("census_match_remat")
def _census_match_remat():
    model, topo, mcfg, step = _build(*BASE)
    text = _compile(model, step).as_text()
    mesh_shape = dict(zip(topo.mesh.axis_names, topo.mesh.devices.shape))
    measured = analyze(text, mesh_shape,
                       partition_axes=topo.partition_axes,
                       replication_axes=topo.replication_axes)["by_stage"]
    gp, sp = policies_from_config(mcfg)
    pred = predict_traffic(model, topo, gp, sp, micro_steps=MICRO,
                           upcast_float_collectives=True)["by_stage"]
    cmp = compare_census(pred, measured)
    detail = {}
    for stage, row in cmp.items():
        p_, m_ = row["predicted_wire_bytes"], row["measured_wire_bytes"]
        assert p_ > 0 and m_ > 0, (stage, row)
        assert abs(m_ - p_) <= 0.02 * p_, (stage, row)
        pc, mc = pred[stage]["count"], measured[stage]["count"]
        assert pc == mc, f"{stage}: count predicted {pc} != measured {mc}"
        detail[stage] = {"bytes": m_, "count": mc}
    RESULTS["census_match_remat_detail"] = detail


# ---------------------------------------------------------------------------
@check("carried_buffer_census")
def _carried_buffer_census():
    by_carry = {}
    for carry in ("stored", "remat"):
        with _carry(carry):
            model, topo, _mcfg, step = _build(*BASE)
            text = _compile(model, step).as_text()
        mesh_shape = dict(zip(topo.mesh.axis_names, topo.mesh.devices.shape))
        by_carry[carry] = analyze(text, mesh_shape)["prefetch"]
    # the stored carry is visible: >0 carried gathers with real payloads
    assert by_carry["stored"]["carried_all_gathers"] > 0
    assert by_carry["stored"]["carried_buffer_bytes"] > 0
    # remat keeps the double-buffered FORWARD (the lookahead gather still
    # flows into the scan carry) — what it drops is the backward residual,
    # which remat_lowers_peak measures via the compiled temp bytes.
    assert by_carry["remat"]["carried_all_gathers"] > 0
    RESULTS["carried_buffer_census_detail"] = by_carry


# ---------------------------------------------------------------------------
@check("offload_lowers_peak")
def _offload_lowers_peak():
    rows = {
        "host_carry": _footprint_cell(
            "offload/host_carry", *BASE, carry_offload="host"),
        "host_carry_opt": _footprint_cell(
            "offload/host_carry_opt", *BASE, carry_offload="host",
            offload_opt=True),
    }
    with _carry("stored"):
        rows["stored"] = _footprint_cell("offload/stored", *BASE)
    s, hc, ho = rows["stored"], rows["host_carry"], rows["host_carry_opt"]
    # the freed carry residual: predicted AND compiled temp bytes drop
    assert hc["predicted_temp_bytes"] < s["predicted_temp_bytes"], rows
    assert hc["measured_temp_bytes"] < s["measured_temp_bytes"], rows
    # offloaded moments leave the donated args (8 bytes/shard element);
    # _footprint_cell already asserted predicted args == compiled args
    assert ho["predicted_args_bytes"] < s["predicted_args_bytes"], rows
    assert ho["measured_args_bytes"] < s["measured_args_bytes"], rows
    # and the end-to-end peak (args + temps) shrinks on both ledgers
    for r in (hc, ho):
        assert r["predicted_args_bytes"] + r["predicted_temp_bytes"] \
            < s["predicted_args_bytes"] + s["predicted_temp_bytes"], rows
        assert r["measured_args_bytes"] + r["measured_temp_bytes"] \
            < s["measured_args_bytes"] + s["measured_temp_bytes"], rows
    RESULTS["offload_lowers_peak_detail"] = rows


# the memplan suite's matrix cells (one contract cell per named check)
RESULTS["cells"] = MS.contract_cells(
    "memplan", RESULTS,
    dict(model="llama3.2-1b-smoke", micro_steps=MICRO,
         global_batch=GLOBAL_BATCH, seq=SEQ))
print(json.dumps(RESULTS, indent=1, default=str))
if "--check" in sys.argv:
    MS.exit_check(RESULTS, "memplan smoke gate")
