"""Elastic preemption survival: fault-injection mechanics (device-free) and
the kill-a-device resume matrix (8-virtual-device subprocess harness,
tests/elastic_harness.py — shared via the session-scoped ``elastic_results``
fixture so the subprocess runs once)."""

import json

import pytest

from repro.core.autotune import resolve_world
from repro.core.faults import (
    CrashDuringSaveError, FaultPlan, GrowthError, PreemptionError,
    StragglerError, WorldChangeError,
)
from repro.core.mics import MiCSConfig
from repro.core.topology import elastic_host_topology

# ---------------------------------------------------------------------------
# FaultPlan mechanics (no devices, no jax arrays)
# ---------------------------------------------------------------------------


def test_events_fire_exactly_once_at_their_step():
    plan = FaultPlan().preempt(3, devices=4, notice=False).grow(7, devices=4)
    plan(0)
    plan(1)   # nothing scheduled: no raise
    with pytest.raises(PreemptionError) as e:
        plan(3)
    assert e.value.lost == 4 and e.value.gained == 0 and not e.value.notice
    plan(3)   # one-shot: the replayed step does not re-raise
    with pytest.raises(GrowthError) as e:
        plan(7)
    assert e.value.gained == 4 and e.value.notice
    assert plan.pending() == []
    assert [ev["kind"] for ev in plan.log] == ["preempt", "grow"]


def test_world_change_hierarchy_and_slow_evict():
    assert issubclass(PreemptionError, WorldChangeError)
    assert issubclass(GrowthError, WorldChangeError)
    plan = FaultPlan(slow_base_s=0.0).slow(2, factor=5.0)    # flag-only
    plan(2)   # no eviction: just (zero, here) delay
    plan2 = FaultPlan(slow_base_s=0.0).slow(1, factor=2.0, evict=True)
    with pytest.raises(StragglerError):
        plan2(1)


def test_crash_during_save_hook_truncates_manifest(tmp_path):
    class FakeCkpt:
        fault_hook = None

    ck = FakeCkpt()
    plan = FaultPlan().crash_during_save(5).bind(ck)
    assert ck.fault_hook == plan._save_hook
    meta = {"step": 5, "data_cursor": 5, "mesh_axes": {"shard": 2}}
    with pytest.raises(CrashDuringSaveError):
        ck.fault_hook("pre_manifest", tmp_path, meta)
    # the corpse a mid-write kill leaves: a manifest that does not parse
    corpse = (tmp_path / "manifest.json").read_text()
    with pytest.raises(ValueError):
        json.loads(corpse)
    # other phases and other steps are untouched, and the event is one-shot
    ck.fault_hook("pre_manifest", tmp_path, {"step": 6})
    ck.fault_hook("pre_manifest", tmp_path, meta)
    assert plan.pending() == []


def test_describe_round_trips_the_timeline():
    plan = FaultPlan().preempt(2, devices=1).slow(4).crash_during_save(6)
    d = plan.describe()
    assert [e["kind"] for e in d["events"]] == \
        ["preempt", "slow", "crash_during_save"]
    assert d["fired"] == []


# ---------------------------------------------------------------------------
# resolve_world / elastic_host_topology (device-free policy half)
# ---------------------------------------------------------------------------


def test_resolve_world_keep_rule_shrinks_to_largest_divisor():
    # no budget: keep p where it divides, else the largest divisor below it
    p, mcfg2, info = resolve_world(None, MiCSConfig(), n_devices=6, tp=1,
                                   partition_size=4)
    assert p == 3 and info["rule"] == "keep"
    p, _, _ = resolve_world(None, MiCSConfig(), n_devices=8, tp=2,
                            partition_size=2)
    assert p == 2
    p, _, info = resolve_world(None, MiCSConfig(), n_devices=2, tp=1,
                               partition_size=4)
    assert p == 2 and info["data_extent"] == 2


def test_resolve_world_rejects_tp_nondivisible_world():
    with pytest.raises(ValueError, match="TP-local"):
        resolve_world(None, MiCSConfig(), n_devices=6, tp=4)
    with pytest.raises(ValueError):
        resolve_world(None, MiCSConfig(), n_devices=0, tp=1)


def test_elastic_host_topology_validates_factorization():
    with pytest.raises(ValueError, match="does not factor"):
        elastic_host_topology(3, 2, tp=1)
    with pytest.raises(ValueError, match="at least one"):
        elastic_host_topology(0, 1, tp=1)


# ---------------------------------------------------------------------------
# the kill-a-device matrix (subprocess harness; one run per session)
# ---------------------------------------------------------------------------

ELASTIC_CHECKS = [
    "kill_pod_resume_bitwise",
    "grow_back_resume_bitwise",
    "repick_keep_rule_bitwise",
    "resolve_scale_repick",
    "data_continuity",
    "straggler_flagged",
    "crash_mid_save",
    "reshard_roundtrip",
    "reshard_moments",
]


@pytest.mark.parametrize("name", ELASTIC_CHECKS)
def test_elastic_harness(elastic_results, name):
    res = elastic_results[name]
    assert res["ok"], f"{name}: {res.get('err')}\n{res.get('tb', '')}"


def test_elastic_summary_ledger(elastic_results):
    s = elastic_results["summary"]
    assert s["restarts"] == 1 and s["world_changes"] == 2
    assert s["emergency_saves"] == 1
    assert all(s["resume_bitwise"].values()), s["resume_bitwise"]
