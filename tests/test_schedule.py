"""Boundary-scheduler tests: plan/config units on one device, plus the
8-virtual-device harness (tests/schedule_harness.py) asserting bitwise
serial==bucketed equivalence across bucket sizes (incl. one-bucket and
bucket>total-bytes degenerate cases), gather topologies and wire dtypes,
the HLO-census evidence that hop-2 runs at bucket granularity interleaved
with boundary compute, the approximate-clip pipeline's degenerate/bounded
-divergence guarantees (clip-inactive equivalence, zero-grad, int8 hop-2
composition, convergence within APPROX_CLIP_LOSS_RTOL, AdamW census
interleave), and the host-offload cells' bitwise equivalence."""

import pathlib

import pytest

from harness_util import run_harness
from repro.core.flat_param import bucket_elems, partition_buckets
from repro.core.mics import MiCSConfig
from repro.core.schedule import BoundaryPlan, BucketRef, plan_boundary

HARNESS = pathlib.Path(__file__).parent / "schedule_harness.py"


# ---------------------------------------------------------------------------
# plan / config units (single device)
# ---------------------------------------------------------------------------

def test_bucket_helpers_validate():
    with pytest.raises(ValueError):
        bucket_elems(0.0)
    with pytest.raises(ValueError):
        partition_buckets(100, -1.0)
    assert bucket_elems(1e-9) == 1            # floor at one element
    assert partition_buckets(3, 1e-9) == ((0, 1), (1, 2), (2, 3))


def test_boundary_config_validated():
    with pytest.raises(ValueError):
        MiCSConfig(boundary_schedule="pipelined")
    with pytest.raises(ValueError):
        MiCSConfig(hop2_bucket_mb=0.0)
    with pytest.raises(ValueError):
        BoundaryPlan(mode="eager", bucket_mb=1.0, shard_elems={}, buckets=())


def test_clip_offload_config_validated():
    with pytest.raises(ValueError):
        MiCSConfig(clip_mode="running")
    with pytest.raises(ValueError):   # approx needs the bucket pipeline
        MiCSConfig(clip_mode="approx", boundary_schedule="serial")
    MiCSConfig(clip_mode="approx", boundary_schedule="bucketed")
    with pytest.raises(ValueError):
        MiCSConfig(carry_offload="nvme")
    with pytest.raises(ValueError):   # host carry offloads the stored carry
        MiCSConfig(carry_offload="host", prefetch=False)
    MiCSConfig(carry_offload="host", prefetch=True)
    with pytest.raises(ValueError):
        BoundaryPlan(mode="bucketed", bucket_mb=1.0, shard_elems={},
                     buckets=(), clip_mode="stale")
    with pytest.raises(ValueError):   # serial has no pipeline to hide under
        BoundaryPlan(mode="serial", bucket_mb=1.0, shard_elems={},
                     buckets=(), clip_mode="approx")


def test_plan_boundary_static_structure(topo1):
    from repro.configs import get_config, smoke_variant
    from repro.models.build import build_model

    model = build_model(smoke_variant(get_config("llama3.2-1b")), tp=1)
    huge = plan_boundary(model, topo1, mode="bucketed", bucket_mb=1e6)
    assert huge.n_buckets == len(model.all_pools())
    tiny = plan_boundary(model, topo1, mode="bucketed", bucket_mb=0.01)
    assert tiny.n_buckets > huge.n_buckets
    # canonical order: pools in all_pools() order, offsets ascending
    names = [p.name for p in model.all_pools()]
    seen = [b.pool for b in tiny.buckets]
    assert seen == sorted(seen, key=names.index)
    for name in names:
        offs = [b.lo for b in tiny.pool_buckets(name)]
        assert offs == sorted(offs)
    d = tiny.describe()
    assert d["n_buckets"] == tiny.n_buckets
    assert d["max_bucket_bytes"] <= int(0.01 * 1e6)
    assert BucketRef("x", 3, 10).elems == 7


def test_autotune_ranks_bucket_axis():
    """policy='auto' must carry the boundary schedule into the config."""
    import dataclasses

    from repro.core.autotune import (
        HOP2_BUCKET_MB_CANDIDATES, enumerate_hop2_schedules, resolve_config,
    )
    from test_autotune import StubModel, topo_single

    topo = topo_single(p=16, repl=2)
    axis = enumerate_hop2_schedules(topo)
    assert ("serial", 32.0) in axis
    assert {mb for b, mb in axis if b == "bucketed"} \
        == set(HOP2_BUCKET_MB_CANDIDATES)
    mcfg = MiCSConfig(policy="auto", link_profile="efa-100g", micro_steps=4)
    resolved, plan = resolve_config(mcfg, StubModel(), topo)
    assert resolved.boundary_schedule in ("serial", "bucketed")
    assert resolved.hop2_bucket_mb == plan.chosen.hop2_bucket_mb
    assert {c.boundary for c in plan.candidates} == {"serial", "bucketed"}
    # exposed <= total for every candidate, strict for some bucketed one
    for c in plan.candidates:
        assert c.t_hop2_exposed_s <= c.t_hop2_total_s + 1e-18
    assert any(c.boundary == "bucketed"
               and c.t_hop2_exposed_s < c.t_hop2_total_s
               for c in plan.candidates)
    d = plan.chosen.describe()
    assert {"boundary", "hop2_bucket_mb", "t_hop2_exposed_s"} <= set(d)
    assert dataclasses.asdict(resolved)["hop2_bucket_mb"] > 0


# ---------------------------------------------------------------------------
# multi-device harness
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def harness_results():
    return run_harness(HARNESS)


CHECKS = [
    "bucket_plan", "bitwise_bucket_sizes", "bitwise_topologies",
    "bitwise_compress", "census_interleave",
    "approx_clip_inactive", "approx_zero_grad",
    "approx_clip_active_bounded", "approx_int8_hop2",
    "approx_census_interleave", "offload_host_bitwise",
]


@pytest.mark.parametrize("name", CHECKS)
def test_schedule_check(harness_results, name):
    res = harness_results.get(name)
    assert res is not None, f"harness did not run {name}"
    assert res["ok"], f"{name}: {res.get('err')}\n{res.get('tb', '')}"


def test_census_interleave_counts(harness_results):
    detail = harness_results.get("census_interleave_detail")
    assert detail is not None
    assert detail["bucketed"]["hop2_ops"] > detail["serial"]["hop2_ops"]
    assert detail["bucketed"]["interleaved"]
    assert detail["bucketed"]["hop2_wire_bytes"] \
        == detail["serial"]["hop2_wire_bytes"]


def test_approx_census_counts(harness_results):
    """The approx pipeline's census signature: same bucket-granular hop-2,
    strictly more compute between the hop-2 ops (the pipelined AdamW)."""
    detail = harness_results.get("approx_census_detail")
    assert detail is not None
    assert detail["approx"]["hop2_ops"] == detail["exact"]["hop2_ops"]
    assert detail["approx"]["compute_between_hop2"] \
        > detail["exact"]["compute_between_hop2"]


def test_approx_convergence_bound(harness_results):
    from repro.core.schedule import APPROX_CLIP_LOSS_RTOL

    detail = harness_results.get("approx_convergence_detail")
    assert detail is not None
    assert detail["rtol"] <= APPROX_CLIP_LOSS_RTOL
    assert detail["final_approx"] < 6.0  # it actually learned


def test_offload_stash_accounting(harness_results):
    detail = harness_results.get("offload_detail")
    assert detail is not None
    assert detail["stash_entries"] > 0
    assert detail["stash_entries"] % 2 == 0  # an m and a v per slot
