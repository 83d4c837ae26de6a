"""Elastic preemption-survival harness: the kill-a-device test matrix.

Runs in a subprocess with 8 virtual CPU devices (the main pytest process
keeps its ambient device set).  Prints one JSON object with named check
results; tests/test_elastic.py and tests/test_checkpoint.py assert on them,
and ``--check`` mode is the CI bench smoke gate (artifact
BENCH_elastic_smoke.json: restart counts + resume-bitwise flags).

Checks:

  kill_pod_resume_bitwise   train on pod=2/p=2/tp=2 (8 devices) under an
                            ``hbm_budget_gb`` picked so §3.1 forces p=2;
                            abruptly preempt one pod (4 devices, no notice)
                            mid-run.  The loop rolls back to the newest
                            complete checkpoint, re-runs resolve_scale for
                            the 4-device world, rebuilds the mesh and
                            resumes — with a loss trajectory and final
                            params BITWISE identical to a cold
                            ``elastic_restart`` of the same checkpoint on
                            the same surviving topology.
  grow_back_resume_bitwise  the preempted capacity returns (grow 4 -> 8
                            with notice): emergency save at the fire step,
                            zero steps lost, resumed trajectory bitwise vs
                            a cold restore on the regrown topology.
  repick_keep_rule_bitwise  no-budget world change (8 -> 2 devices, tp=1):
                            the keep rule shrinks p 4 -> 2 (largest
                            dividing group), notice path loses zero steps,
                            bitwise vs cold restore.
  resolve_scale_repick      the ledger's partition size equals a direct
                            resolve_scale call for the degraded/regrown
                            extents, and the budget really separates p=1
                            from p=2 (no hardcoded answers).
  data_continuity           recorded per-batch fingerprints across both
                            restart boundaries: cursors replay exactly the
                            rolled-back span (abrupt kill) or nothing at
                            all (with notice), and never skip a batch.
  straggler_flagged         an injected slow step trips the EWMA detector;
                            an injected eviction rides rollback-and-retry.
  crash_mid_save            the checkpoint writer dies mid-write (truncated
                            manifest in a ``.tmp`` dir): the loop's next
                            rollback restores the older *complete* step,
                            and the retried save restores the cadence.
  reshard_roundtrip         save -> restore -> save across p=2 -> p=4 ->
                            p=2 topologies is bitwise lossless.
  offload_cross_topology    ``offload_opt=True`` restore onto a different
                            topology resets the host-stashed moments
                            EXPLICITLY (meta["host_stash"], warning) and
                            training continues; same-topology restore
                            re-imports them.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import dataclasses
import hashlib
import json
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

import repro.runtime.train_loop as TL
from repro.bench import measure as MS
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config, smoke_variant
from repro.core import memplan as M
from repro.core.autotune import resolve_scale
from repro.core.comm import policies_from_config
from repro.core.faults import FaultPlan
from repro.core.hostoffload import export_stash, stash_clear, stash_size
from repro.core.linkmodel import GIB
from repro.core.mics import MiCSConfig, build_train_step, init_state
from repro.core.topology import MiCSTopology, elastic_host_topology, make_host_mesh
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.runtime.train_loop import (
    ElasticConfig, LoopConfig, elastic_restart, resize_for_world, train,
)

RESULTS = {}
CTX = {}      # cross-check shared state (ledgers, recorded batches)

check = MS.make_check(RESULTS)


class RecordingLM(SyntheticLM):
    """SyntheticLM that fingerprints every batch the train loop consumes —
    the replay/skip evidence of the data-continuity check."""

    served: list = []

    def global_step_batch(self, step):
        b = super().global_step_batch(step)
        RecordingLM.served.append(
            (int(step), hashlib.sha1(b["tokens"].tobytes()).hexdigest()))
        return b


TL.SyntheticLM = RecordingLM   # train() instantiates via its module global

CFG = smoke_variant(get_config("llama3.2-1b"))
OC = OptConfig(total_steps=40, warmup_steps=0, lr_max=1e-3)
DC = DataConfig(vocab=CFG.vocab, seq=32, global_batch=8, micro_steps=2)
COLD_DATA = SyntheticLM(DC)    # un-recorded source for cold reference runs


def _run_cold(step_fn, state, cursors, data=COLD_DATA):
    losses = []
    for c in cursors:
        batch = jax.tree.map(jnp.asarray, data.global_step_batch(c))
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


def _tree_equal(a, b, msg=""):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=msg), a, b)


# ---------------------------------------------------------------------------
# budget: picked so the §3.1 rule has a real decision to make — p=2 with the
# default carry fits in BOTH worlds (8 and 4 devices at tp=2), while p=1
# overflows under every carry.  Computed from the footprint model, never
# hardcoded.
# ---------------------------------------------------------------------------

def _pick_budget(model, mcfg, extents):
    gp, sp = policies_from_config(mcfg)
    carries = ("remat", "host") if gp.prefetch else ("remat",)

    def fp(p, extent, carry):
        g2 = dataclasses.replace(
            gp, carry_offload="host" if carry == "host" else "none")
        grid = M.DeviceGrid(partition_size=p, replication_degree=extent // p)
        return M.predict_footprint(
            model, grid, g2, sp, micro_steps=mcfg.micro_steps,
            boundary=mcfg.boundary_schedule,
            hop2_bucket_mb=mcfg.hop2_bucket_mb,
            offload_opt=mcfg.offload_opt).total_bytes

    need = max(fp(2, e, "remat") for e in extents)           # p=2 must fit
    cap = min(fp(1, e, c) for e in extents for c in carries)  # p=1 must not
    assert need < cap, f"no separating budget: p2={need} p1={cap}"
    return (need + cap) / 2 / GIB, need / GIB, cap / GIB


MODEL2 = build_model(CFG, tp=2)
BUDGET_GB, FP_P2_GIB, FP_P1_GIB = _pick_budget(
    MODEL2, MiCSConfig(micro_steps=2), extents=(4, 2))
MCFG_B = MiCSConfig(micro_steps=2, hbm_budget_gb=BUDGET_GB)

KILL_DIR = tempfile.mkdtemp(prefix="elastic_kill_")


# ---------------------------------------------------------------------------
@check("kill_pod_resume_bitwise")
def _kill_pod():
    topo8 = MiCSTopology(make_host_mesh(2, 1, 2, 2))   # pod=2, p=2, tp=2
    lc = LoopConfig(total_steps=10, checkpoint_every=3, log_every=0,
                    checkpoint_dir=KILL_DIR, seed=0)
    plan = FaultPlan().preempt(5, devices=4, notice=False)  # abrupt pod loss
    RecordingLM.served = []
    stats = train(MODEL2, topo8, MCFG_B, OC, DC, lc,
                  fault_injector=plan, elastic=ElasticConfig())
    CTX["kill_stats"] = stats
    CTX["kill_served"] = list(RecordingLM.served)

    assert stats.restarts == 1 and len(stats.world_changes) == 1, vars(stats)
    wc = stats.world_changes[0]
    assert wc["kind"] == "preempt" and wc["lost"] == 4 and not wc["notice"]
    assert wc["at_step"] == 5 and wc["world"] == 4
    assert wc["resumed_step"] == 3        # newest complete ckpt (every=3)
    assert wc["rule"] == "resolve_scale" and wc["partition_size"] == 2, wc
    # 5 losses on 8 devices (steps 0-4) + 7 on the survivors (steps 3-9)
    assert len(stats.losses) == 12, len(stats.losses)

    # cold reference: the same checkpoint, the same surviving topology,
    # through the same resize_for_world the loop used
    topo4, mcfg4, info4 = resize_for_world(
        MODEL2, MCFG_B, 4, tp=2, partition_size=topo8.partition_size)
    assert info4["partition_size"] == wc["partition_size"]
    _, cold_state, cold_step, meta = elastic_restart(
        KILL_DIR, CFG, topo4, mcfg4, OC, step=3)
    assert meta["data_cursor"] == 3
    cold_state, cold_losses = _run_cold(cold_step, cold_state, range(3, 10))

    np.testing.assert_array_equal(
        np.float64(stats.losses[5:]), np.float64(cold_losses),
        err_msg="post-preemption trajectory is not bitwise-identical to the "
                "cold restore on the surviving topology")
    final, _ = Checkpointer(KILL_DIR).restore(MODEL2, topo4, step=10)
    _tree_equal(final, cold_state, "final params diverge from cold restore")
    RESULTS["kill_pod_detail"] = {
        "losses": len(stats.losses), "restarts": stats.restarts,
        "ledger": wc, "resume_bitwise": True,
        "budget_gb": BUDGET_GB, "fp_p2_gib": FP_P2_GIB,
        "fp_p1_gib": FP_P1_GIB,
    }


# ---------------------------------------------------------------------------
@check("grow_back_resume_bitwise")
def _grow_back():
    # continue in the same checkpoint dir: the 4-device survivors regrow to 8
    topo4 = elastic_host_topology(4, 2, tp=2)
    lc = LoopConfig(total_steps=16, checkpoint_every=4, log_every=0,
                    checkpoint_dir=KILL_DIR, seed=0)
    plan = FaultPlan().grow(12, devices=4)
    RecordingLM.served = []
    stats = train(MODEL2, topo4, MCFG_B, OC, DC, lc,
                  fault_injector=plan, elastic=ElasticConfig())
    CTX["grow_stats"] = stats
    CTX["grow_served"] = list(RecordingLM.served)

    assert len(stats.world_changes) == 1, stats.world_changes
    wc = stats.world_changes[0]
    assert wc["kind"] == "grow" and wc["gained"] == 4 and wc["world"] == 8
    # grow announcements come with notice: emergency save, zero lost steps
    assert stats.emergency_saves == 1
    assert wc["resumed_step"] == wc["at_step"] == 12, wc
    assert wc["partition_size"] == 2, wc
    assert len(stats.losses) == 6      # 10,11 on 4 devices + 12-15 on 8

    topo8, mcfg8, _ = resize_for_world(MODEL2, MCFG_B, 8, tp=2,
                                       partition_size=2)
    _, cold_state, cold_step, meta = elastic_restart(
        KILL_DIR, CFG, topo8, mcfg8, OC, step=12)
    assert meta["data_cursor"] == 12 and meta["emergency"] is True
    cold_state, cold_losses = _run_cold(cold_step, cold_state, range(12, 16))
    np.testing.assert_array_equal(
        np.float64(stats.losses[2:]), np.float64(cold_losses),
        err_msg="post-growback trajectory diverges from cold restore")
    final, _ = Checkpointer(KILL_DIR).restore(MODEL2, topo8, step=16)
    _tree_equal(final, cold_state, "final params diverge after grow-back")
    RESULTS["grow_back_detail"] = {
        "ledger": wc, "emergency_saves": stats.emergency_saves,
        "resume_bitwise": True,
    }


# ---------------------------------------------------------------------------
@check("resolve_scale_repick")
def _repick():
    # the ledger's p is a *property* of §3.1, not a hardcoded expectation:
    # a direct resolve_scale call for each world must agree with the loop
    for extent, wc in ((2, CTX["kill_stats"].world_changes[0]),
                       (4, CTX["grow_stats"].world_changes[0])):
        p, carry, plan = resolve_scale(MODEL2, MCFG_B, data_extent=extent)
        assert p == wc["partition_size"], (extent, p, wc)
        assert carry == wc["carry"], (extent, carry, wc)
        assert plan.total_bytes <= BUDGET_GB * GIB
    # and the budget genuinely separates the candidates
    assert FP_P2_GIB < BUDGET_GB < FP_P1_GIB


# ---------------------------------------------------------------------------
@check("data_continuity")
def _continuity():
    # abrupt kill: batch 5 was fetched when the preemption hit, the loop
    # rolled back to step 3 — cursors replay exactly [3,4,5] and then run
    # on; nothing is skipped
    cursors = [c for c, _ in CTX["kill_served"]]
    assert cursors == list(range(6)) + list(range(3, 10)), cursors
    # with notice (grow): batch 12 was fetched, the emergency save kept it
    # current — it is re-fetched once after the rebuild, nothing replays
    cursors = [c for c, _ in CTX["grow_served"]]
    assert cursors == [10, 11, 12] + list(range(12, 16)), cursors
    # fingerprints: the same cursor always serves the same bytes (across
    # the restart boundary AND across loader instances)
    for served in (CTX["kill_served"], CTX["grow_served"]):
        by_cursor = {}
        for c, h in served:
            assert by_cursor.setdefault(c, h) == h, f"cursor {c} replayed " \
                "with different data"
    fresh = hashlib.sha1(
        SyntheticLM(DC).global_step_batch(3)["tokens"].tobytes()).hexdigest()
    assert dict(CTX["kill_served"])[3] == fresh


# ---------------------------------------------------------------------------
@check("repick_keep_rule_bitwise")
def _keep_rule():
    # no budget: the keep rule shrinks p to the largest dividing group.
    # 8 devices at p=4/tp=1 lose 6 with notice -> 2 devices, p 4 -> 2,
    # emergency save, zero steps lost, bitwise vs cold restore.
    d = tempfile.mkdtemp(prefix="elastic_keep_")
    model = build_model(CFG, tp=1)
    topo = elastic_host_topology(8, 4, tp=1)
    mcfg = MiCSConfig(micro_steps=2)
    dc = DataConfig(vocab=CFG.vocab, seq=32, global_batch=16, micro_steps=2)
    lc = LoopConfig(total_steps=6, checkpoint_every=10, log_every=0,
                    checkpoint_dir=d, seed=0)
    plan = FaultPlan().preempt(3, devices=6, notice=True)
    stats = train(model, topo, mcfg, OC, dc, lc,
                  fault_injector=plan, elastic=ElasticConfig())
    wc = stats.world_changes[0]
    assert wc["rule"] == "keep" and wc["partition_size"] == 2, wc
    assert wc["resumed_step"] == wc["at_step"] == 3   # notice: zero lost
    assert stats.emergency_saves == 1 and len(stats.losses) == 6

    topo2, mcfg2, info = resize_for_world(model, mcfg, 2, tp=1,
                                          partition_size=4)
    assert info["partition_size"] == 2
    _, cold_state, cold_step, meta = elastic_restart(
        d, CFG, topo2, mcfg2, OC, step=3)
    cold_state, cold_losses = _run_cold(cold_step, cold_state, range(3, 6),
                                        data=SyntheticLM(dc))
    np.testing.assert_array_equal(
        np.float64(stats.losses[3:]), np.float64(cold_losses))
    final, _ = Checkpointer(d).restore(model, topo2, step=6)
    _tree_equal(final, cold_state)
    RESULTS["keep_rule_detail"] = {"ledger": wc, "resume_bitwise": True}


# ---------------------------------------------------------------------------
@check("straggler_flagged")
def _straggler():
    d = tempfile.mkdtemp(prefix="elastic_slow_")
    model = build_model(CFG, tp=1)
    topo = elastic_host_topology(2, 2, tp=1)
    lc = LoopConfig(total_steps=10, checkpoint_every=3, log_every=0,
                    checkpoint_dir=d, seed=0)
    # one 6s stall (flag only) + one evicted straggler (rollback path)
    plan = (FaultPlan(slow_base_s=0.5)
            .slow(6, factor=13)
            .slow(8, factor=2, evict=True))
    stats = train(model, topo, MiCSConfig(micro_steps=2), OC, DC, lc,
                  fault_injector=plan, elastic=ElasticConfig())
    assert 6 in stats.straggler_steps, stats.straggler_steps
    assert stats.restarts == 1          # the eviction rode rollback
    # rollback to step-6 ckpt replays 6,7: 8 + 4 losses
    assert len(stats.losses) == 12, len(stats.losses)
    assert all(np.isfinite(stats.losses))
    RESULTS["straggler_detail"] = {
        "straggler_steps": stats.straggler_steps, "restarts": stats.restarts,
        "fired": plan.log,
    }


# ---------------------------------------------------------------------------
@check("crash_mid_save")
def _crash_mid_save():
    d = tempfile.mkdtemp(prefix="elastic_crash_")
    model = build_model(CFG, tp=1)
    topo = elastic_host_topology(2, 2, tp=1)
    lc = LoopConfig(total_steps=8, checkpoint_every=2, log_every=0,
                    checkpoint_dir=d, seed=0)
    # the async step-4 save dies mid-write (truncated manifest in the .tmp
    # dir); the eviction at step 5 then forces a rollback, which must land
    # on step 2 — the newest COMPLETE checkpoint — not the corpse of 4
    plan = (FaultPlan()
            .crash_during_save(4)
            .slow(5, factor=2, evict=True))
    stats = train(model, topo, MiCSConfig(micro_steps=2), OC, DC, lc,
                  fault_injector=plan, elastic=ElasticConfig())
    # 5 losses (0-4) + 6 replayed from step 2 (2-7): rollback skipped the
    # crashed step-4 checkpoint (9 losses would mean it restored from it)
    assert len(stats.losses) == 11, len(stats.losses)
    assert stats.save_failures == 1     # held writer crash surfaced+retried
    ck = Checkpointer(d)
    assert ck.latest_step() == 8        # cadence recovered after the retry
    RESULTS["crash_mid_save_detail"] = {
        "losses": len(stats.losses), "save_failures": stats.save_failures,
        "fired": plan.log,
    }


# ---------------------------------------------------------------------------
@check("reshard_roundtrip")
def _reshard_roundtrip():
    # save -> restore -> save across p=2 -> p=4 -> p=2 is bitwise lossless
    d = tempfile.mkdtemp(prefix="elastic_reshard_")
    model = build_model(CFG, tp=1)
    topo_p2 = elastic_host_topology(4, 2, tp=1)
    topo_p4 = elastic_host_topology(4, 4, tp=1)
    state0 = init_state(model, topo_p2, seed=11)
    ck = Checkpointer(d)
    ck.save(state0, 1, topo=topo_p2, data_cursor=1)
    state_p4, meta = ck.restore(model, topo_p4)
    assert meta["mesh_axes"]["shard"] == 2      # provenance: saved at p=2
    ck.save(state_p4, 2, topo=topo_p4, data_cursor=2)
    state_back, meta2 = ck.restore(model, topo_p2, step=2)
    assert meta2["mesh_axes"]["shard"] == 4
    _tree_equal(state0, state_back,
                "p=2 -> p=4 -> p=2 roundtrip is not bitwise lossless")


# ---------------------------------------------------------------------------
@check("offload_cross_topology")
def _offload_cross_topology():
    d = tempfile.mkdtemp(prefix="elastic_offload_")
    model = build_model(CFG, tp=1)
    topo_p2 = elastic_host_topology(4, 2, tp=1)
    mcfg = MiCSConfig(micro_steps=2, offload_opt=True)
    stash_clear()
    state = init_state(model, topo_p2, seed=3, offload_opt=True)
    step_fn = build_train_step(model, topo_p2, mcfg, OC)
    state, _ = _run_cold(step_fn, state, range(2))   # populate m/v stash
    assert stash_size() > 0
    ck = Checkpointer(d)
    ck.save(state, 2, topo=topo_p2, data_cursor=2, host_stash=export_stash())

    # same topology: the offloaded moments come back
    stash_clear()
    _, meta = ck.restore(model, topo_p2, offload_opt=True)
    assert meta["host_stash"] == {
        "present": True, "restored": True, "reset": None}, meta["host_stash"]
    assert stash_size() > 0

    # different topology: EXPLICIT reset — surfaced in meta, training runs on
    stash_clear()
    topo_p4 = elastic_host_topology(4, 4, tp=1)
    state4, meta4 = ck.restore(model, topo_p4, offload_opt=True)
    hs = meta4["host_stash"]
    assert hs["present"] and not hs["restored"], hs
    assert hs["reset"] == "cross-topology", hs
    step4 = build_train_step(model, topo_p4, mcfg, OC)
    state4, losses = _run_cold(step4, state4, range(2, 4))
    assert all(np.isfinite(losses)), losses
    RESULTS["offload_detail"] = {"same_topo": meta["host_stash"],
                                 "cross_topo": hs}


# ---------------------------------------------------------------------------
# summary ledger for the CI bench artifact (BENCH_elastic_smoke.json)
ks, gs = CTX.get("kill_stats"), CTX.get("grow_stats")
RESULTS["summary"] = {
    "restarts": (ks.restarts if ks else None),
    "world_changes": ((len(ks.world_changes) if ks else 0)
                      + (len(gs.world_changes) if gs else 0)),
    "emergency_saves": (gs.emergency_saves if gs else None),
    "resume_bitwise": {
        name: RESULTS.get(name, {}).get("ok", False)
        for name in ("kill_pod_resume_bitwise", "grow_back_resume_bitwise",
                     "repick_keep_rule_bitwise")
    },
    "budget_gb": BUDGET_GB,
}

# the elastic suite's matrix cells (one contract cell per named check)
RESULTS["cells"] = MS.contract_cells(
    "elastic", RESULTS,
    dict(model="llama3.2-1b-smoke", budget_gb=BUDGET_GB))
print(json.dumps(RESULTS, indent=1, default=str))
if "--check" in sys.argv:
    MS.exit_check(RESULTS, "elastic smoke gate")
