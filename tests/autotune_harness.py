"""Autotuner census-match harness, run in a subprocess with 8 virtual CPU
devices (same pattern as comm_harness.py).  Prints one JSON object with named
check results; tests/test_autotune.py asserts on them.

The property under test is the tentpole contract of core/autotune.py: the
*analytical* per-stage census (``predict_traffic``) equals the *measured*
census (``hlo_stats.analyze(...)['by_stage']``) of the actually-compiled
train step, stage by stage, for every (gather topology x wire dtype) — so
the cost model ranks policies from the same traffic the HLO really has.

Checks:

  census_match_single   3 topologies x 3 wire dtypes on a single-axis
                        partition group (p=4, repl=2 -> hop2 present):
                        per-stage wire bytes within 2% (padding is already
                        in flat_len, so in practice they match exactly),
                        collective counts exactly equal
  census_match_prefetch the double-buffered schedule's counts under each
                        carry: remat, the training default (2*s*stack + 1
                        gathers, s*stack adjoints), stored, which enc-dec
                        decoder pools take (s*stack + 1 gathers,
                        s*(stack+1) adjoints; forced here by
                        harness_util.stored_carry)
  census_match_multi    multi-axis ('pod','shard') partition group: the
                        outer stage is the pod hop, bytes match both stage
                        orders
  census_match_qgz      the int8 qgZ hop-1 wire under all 3 topologies:
                        the grad_rs stages become per-stage all-to-all
                        pairs (int8 q + f32 scales) whose predicted wire
                        bytes and instruction counts match the compiled
                        HLO exactly (ISSUE 4 acceptance)
  auto_plan_census      policy="auto" end to end: resolve_config picks a
                        plan, the step compiled from the resolved config
                        measures the bytes the plan predicted

The prediction side passes ``upcast_float_collectives=True`` because the
XLA CPU backend widens bf16 collectives to f32 on the wire; on TPU the
flag stays False and the same formulas describe the real traffic.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import contextlib
import json
import traceback

import jax.numpy as jnp

from harness_util import stored_carry
from repro.configs import get_config, smoke_variant
from repro.core.autotune import compare_census, predict_traffic, resolve_config
from repro.core.comm import GatherPolicy, SyncPolicy
from repro.core.mics import (
    MiCSConfig, build_train_step, init_state_shapes, make_batch_shapes,
)
from repro.core.topology import MiCSTopology, make_host_mesh
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.roofline.hlo_stats import analyze

RESULTS = {}
MICRO = 2
RTOL = 0.02     # "padding tolerance": flat_len is pre-padded, so ~exact

_WIRE_MCFG = {
    "fp32": dict(gather_dtype=jnp.float32),
    "bf16": dict(gather_dtype=jnp.bfloat16),
    "int8": dict(gather_dtype=jnp.bfloat16, quant_gather=True),
}


def check(name):
    def deco(fn):
        try:
            fn()
            RESULTS[name] = {"ok": True}
        except Exception as e:  # noqa: BLE001
            RESULTS[name] = {
                "ok": False,
                "err": f"{type(e).__name__}: {e}",
                "tb": traceback.format_exc()[-2000:],
            }
        return fn
    return deco


def _mcfg(topology: str, wire: str, prefetch: bool = False,
          hop1: str = "fp32") -> MiCSConfig:
    return MiCSConfig(
        micro_steps=MICRO,
        hierarchical=topology != "flat",
        gather_order=topology if topology != "flat" else "inner_first",
        prefetch=prefetch,
        hop1_wire_dtype=hop1,
        **_WIRE_MCFG[wire],
    )


def _measure(model, topo, mcfg, *, global_batch=16, seq=16):
    step = build_train_step(model, topo, mcfg, OptConfig(total_steps=10))
    text = step.lower(
        init_state_shapes(model),
        make_batch_shapes(model, global_batch, seq, MICRO),
    ).compile().as_text()
    mesh_shape = dict(zip(topo.mesh.axis_names, topo.mesh.devices.shape))
    return analyze(text, mesh_shape,
                   partition_axes=topo.partition_axes,
                   replication_axes=topo.replication_axes)


def _assert_match(model, topo, topology, wire, *, prefetch=False,
                  hop1="fp32", tag=""):
    mcfg = _mcfg(topology, wire, prefetch, hop1)
    measured = _measure(model, topo, mcfg)["by_stage"]
    pred = predict_traffic(
        model, topo,
        GatherPolicy(topology, wire, None, prefetch),
        SyncPolicy(hop1_wire_dtype=hop1),
        micro_steps=MICRO, upcast_float_collectives=True,
    )["by_stage"]
    cmp = compare_census(pred, measured)
    detail = {}
    for stage, row in cmp.items():
        p, m = row["predicted_wire_bytes"], row["measured_wire_bytes"]
        assert p > 0 and m > 0, f"{tag}/{stage}: empty side {row}"
        assert abs(m - p) <= RTOL * p, \
            f"{tag}/{stage}: predicted {p} != measured {m}"
        pc, mc = pred[stage]["count"], measured[stage]["count"]
        assert pc == mc, f"{tag}/{stage}: count predicted {pc} != {mc}"
        detail[stage] = {"bytes": m, "ratio": row["ratio"], "count": mc}
    return detail


def _single_axis():
    cfg = smoke_variant(get_config("llama3.2-1b"))
    topo = MiCSTopology(make_host_mesh(1, 2, 4, 1),
                        partition_axes=("shard",),
                        replication_axes=("pod", "repl"))
    return build_model(cfg, tp=1), topo


# ---------------------------------------------------------------------------
@check("census_match_single")
def _census_single():
    model, topo = _single_axis()
    detail = {}
    for topology in ("flat", "inner_first", "outer_first"):
        for wire in ("fp32", "bf16", "int8"):
            detail[f"{topology}/{wire}"] = _assert_match(
                model, topo, topology, wire, tag=f"{topology}/{wire}")
    RESULTS["census_match_single_detail"] = detail


# ---------------------------------------------------------------------------
@check("census_match_prefetch")
def _census_prefetch():
    model, topo = _single_axis()
    detail = {}
    for carry in ("remat", "stored"):
        with stored_carry() if carry == "stored" \
                else contextlib.nullcontext():
            detail[carry] = _assert_match(
                model, topo, "inner_first", "bf16", prefetch=True,
                tag=f"prefetch/{carry}")
    RESULTS["census_match_prefetch_detail"] = detail


# ---------------------------------------------------------------------------
@check("census_match_multi")
def _census_multi():
    cfg = smoke_variant(get_config("llama3.2-1b"))
    topo = MiCSTopology(make_host_mesh(2, 1, 4, 1),
                        partition_axes=("pod", "shard"),
                        replication_axes=("repl",))
    model = build_model(cfg, tp=1)
    detail = {}
    for topology in ("inner_first", "outer_first"):
        detail[topology] = _assert_match(
            model, topo, topology, "bf16", tag=f"multi/{topology}")
    # the slow-axis hop exists and is the outer stage
    for topology, d in detail.items():
        assert "param_gather.outer" in d, (topology, d)
    RESULTS["census_match_multi_detail"] = detail


# ---------------------------------------------------------------------------
@check("census_match_qgz")
def _census_qgz():
    """int8 qgZ hop-1: per-stage all-to-all wire bytes and counts are
    instruction-exact for every topology (the ISSUE 4 acceptance check)."""
    model, topo = _single_axis()
    detail = {}
    for topology in ("flat", "inner_first", "outer_first"):
        detail[topology] = _assert_match(
            model, topo, topology, "bf16", hop1="int8",
            tag=f"qgz/{topology}")
        assert any(k.startswith("grad_rs") for k in detail[topology])
    RESULTS["census_match_qgz_detail"] = detail


# ---------------------------------------------------------------------------
@check("auto_plan_census")
def _auto_plan_census():
    model, topo = _single_axis()
    mcfg = MiCSConfig(micro_steps=MICRO, policy="auto", link_profile="v5e",
                      prefetch=False)
    resolved, plan = resolve_config(mcfg, model, topo)
    assert plan is not None and resolved.policy == "manual"
    g = plan.chosen.gather
    measured = _measure(model, topo, resolved)["by_stage"]
    pred = predict_traffic(
        model, topo, g, plan.chosen.sync, micro_steps=MICRO,
        upcast_float_collectives=True)["by_stage"]
    cmp = compare_census(pred, measured)
    for stage, row in cmp.items():
        p, m = row["predicted_wire_bytes"], row["measured_wire_bytes"]
        assert abs(m - p) <= RTOL * max(p, 1.0), (stage, row)
    RESULTS["auto_plan_census_detail"] = {
        "chosen": plan.chosen.describe()["gather"],
        "stages": {k: v["measured_wire_bytes"] for k, v in cmp.items()},
    }


print(json.dumps(RESULTS, indent=1, default=str))
