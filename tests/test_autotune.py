"""Autotuner + link-model tests.

Pure units run on any ambient device set (the ranking math never touches
devices — model/topology are duck-typed stubs); the census-match property
tests run through the 8-virtual-device subprocess harness
(tests/autotune_harness.py), comparing the analytical per-stage byte counts
against the measured ``hlo_stats.analyze`` census for every
(topology x wire dtype), plus ``policy="auto"`` end to end.
"""

import dataclasses
import json
import pathlib

import pytest

from harness_util import run_harness
from repro.core.autotune import (
    Plan, compare_census, enumerate_candidates, gather_stages,
    predict_traffic, rank_policies, resolve_config,
)
from repro.core.comm import CommEngine, GatherPolicy, SyncPolicy
from repro.core.linkmodel import (
    EFA_100G, PROFILES, V5E, custom_profile, gbps, get_profile,
)
from repro.core.mics import MiCSConfig

HARNESS = pathlib.Path(__file__).parent / "autotune_harness.py"


# ---------------------------------------------------------------------------
# device-free stubs: the tuner only reads sizes and names
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StubTopo:
    axes: dict
    partition_axes: tuple
    replication_axes: tuple

    def axis_size(self, name):
        return self.axes[name]

    @property
    def partition_size(self):
        out = 1
        for a in self.partition_axes:
            out *= self.axes[a]
        return out

    @property
    def replication_degree(self):
        out = 1
        for a in self.replication_axes:
            out *= self.axes[a]
        return out


@dataclasses.dataclass(frozen=True)
class StubPool:
    name: str


class StubModel:
    """Three pools shaped like a small LM: embed + scanned stack + head."""

    def __init__(self, stack=8, flat_len=65536):
        self.pools = (StubPool("layers"),)
        self._shapes = {
            "embed": (1, 1, 16384),
            "layers": (stack, 1, flat_len),
            "head": (1, 1, 20480),
        }

    def all_pools(self):
        return (StubPool("embed"), StubPool("layers"), StubPool("head"))

    def global_flat_shapes(self):
        return dict(self._shapes)


def topo_single(p=16, repl=2):
    return StubTopo({"shard": p, "repl": repl},
                    ("shard",), ("repl",))


def topo_multi(pods=2, shard=8):
    return StubTopo({"pod": pods, "shard": shard, "repl": 1},
                    ("pod", "shard"), ("repl",))


# ---------------------------------------------------------------------------
# linkmodel units
# ---------------------------------------------------------------------------

def test_named_profiles_and_lookup():
    for name in ("v5e", "efa-100g", "efa-400g"):
        p = get_profile(name)
        assert p.name == name
        assert p.intra.bandwidth > 0 and p.inter.bandwidth > 0
        assert p.node_size > 1
    # the heterogeneous-link profiles the paper's argument rests on
    assert V5E.intra.bandwidth > V5E.inter.bandwidth
    assert EFA_100G.intra.bandwidth > EFA_100G.inter.bandwidth
    assert get_profile(V5E) is V5E
    with pytest.raises(KeyError):
        get_profile("nvlink-9000")


def test_gbps_and_custom_constructor():
    assert gbps(100) == 12.5e9          # 100 Gbps EFA = 12.5 GB/s
    assert EFA_100G.inter.bandwidth == gbps(100)
    prof = custom_profile("test-table", intra_bw=100e9, inter_bw=1e9,
                          node_size=4, register=True)
    assert PROFILES["test-table"] is prof
    assert get_profile("test-table").node_size == 4


def test_ring_time_alpha_beta():
    p = custom_profile("rt", intra_bw=10e9, inter_bw=1e9, node_size=4,
                       alpha_intra=1e-6, alpha_inter=10e-6)
    # 8 participants, 7 hops, 7 MB on the wire at 1 GB/s + 7 * 10us
    t = p.ring_time("inter", 8, 7e6)
    assert t == pytest.approx(7 * 10e-6 + 7e6 / 1e9)
    assert p.ring_time("intra", 1, 1e9) == 0.0
    assert p.group_tier(range(4)) == "intra"
    assert p.group_tier([0, 4]) == "inter"


# ---------------------------------------------------------------------------
# stage algebra
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p,inner", [(4, 2), (8, 2), (8, 4), (16, 4)])
def test_staged_bytes_equal_flat_bytes(p, inner):
    """Hierarchical staging moves bytes between tiers, never saves them:
    sum over stages of per-participant wire fractions == (p-1)/p."""
    topo = StubTopo({"shard": p, "repl": 1}, ("shard",), ("repl",))
    for topology in ("flat", "inner_first", "outer_first"):
        stages = gather_stages(topology, topo, inner)
        total = sum(st.wire_frac for st in stages)
        assert total == pytest.approx((p - 1) / p), (topology, stages)


def test_outer_first_minimizes_slow_tier_bytes():
    """Only M(o-1)/p of an outer-first gather crosses the slow tier vs
    M(o-1)/o for inner-first — the paper's §3.3 argument in one assert."""
    topo = StubTopo({"shard": 16, "repl": 1}, ("shard",), ("repl",))
    by = {
        t: {st.label: st.wire_frac for st in gather_stages(t, topo, 4)}
        for t in ("inner_first", "outer_first")
    }
    assert by["outer_first"]["outer"] < by["inner_first"]["outer"]
    assert by["outer_first"]["outer"] == pytest.approx(3 / 16)
    assert by["inner_first"]["outer"] == pytest.approx(3 / 4)


def test_predict_traffic_stage_structure():
    model, topo = StubModel(), topo_single(p=16, repl=2)
    pred = predict_traffic(model, topo,
                           GatherPolicy("inner_first", "bf16", 4, False),
                           SyncPolicy(), micro_steps=2)
    stages = pred["by_stage"]
    assert set(stages) == {"param_gather.inner", "param_gather.outer",
                           "grad_rs.inner", "grad_rs.outer", "hop2"}
    # hop-2 bf16 compression halves exactly the hop2 stage
    pred_c = predict_traffic(model, topo,
                             GatherPolicy("inner_first", "bf16", 4, False),
                             SyncPolicy("2hop", "bf16"), micro_steps=2)
    assert pred_c["by_stage"]["hop2"]["wire_bytes"] == \
        pytest.approx(stages["hop2"]["wire_bytes"] / 2)
    for k in ("param_gather.inner", "grad_rs.outer"):
        assert pred_c["by_stage"][k]["wire_bytes"] == \
            pytest.approx(stages[k]["wire_bytes"])


def test_compare_census_filters_to_engine_stages():
    got = compare_census(
        {"param_gather.flat": {"wire_bytes": 10.0}},
        {"param_gather.flat": {"wire_bytes": 10.0},
         "model_gather": {"wire_bytes": 99.0},
         "tp_allreduce": {"wire_bytes": 99.0}},
    )
    assert set(got) == {"param_gather.flat"}
    assert got["param_gather.flat"]["ratio"] == 1.0


# ---------------------------------------------------------------------------
# ranking regressions
# ---------------------------------------------------------------------------

def test_auto_picks_outer_first_on_slow_inter_pod():
    """The ISSUE regression: when the profile's inter-pod bandwidth is far
    below intra-pod, the paper-faithful 3-stage outer-first gather must win
    (it is the only topology that sends just M(o-1)/p over the slow tier)."""
    prof = custom_profile("slow-pod", intra_bw=100e9, inter_bw=1e9,
                          node_size=8)
    plan = rank_policies(StubModel(), topo_multi(pods=2, shard=8), prof,
                         micro_steps=4, prefetch=False)
    assert plan.chosen.gather.topology == "outer_first"
    # and the winner's slow-tier bytes are the minimum among candidates of
    # the same (lossless) numerics — the lossy int8/qgZ rows move even
    # fewer bytes but are not eligible without opt-in
    lossless = [c for c in plan.candidates
                if not (c.lossy_wire or c.lossy_hop2 or c.lossy_hop1)]
    assert plan.chosen.inter_wire_bytes == pytest.approx(
        min(c.inter_wire_bytes for c in lossless))


def test_uniform_links_never_pick_outer_first():
    """With a homogeneous network the reorder stage is pure cost — the
    3-stage schedule must not win."""
    prof = custom_profile("uniform", intra_bw=50e9, inter_bw=50e9,
                          node_size=16, alpha_inter=1e-6)
    plan = rank_policies(StubModel(), topo_single(p=16), prof,
                         micro_steps=4, prefetch=False)
    assert plan.chosen.gather.topology != "outer_first"


def test_lossy_candidates_ranked_but_not_chosen():
    prof = custom_profile("lossy-test", intra_bw=100e9, inter_bw=1e9,
                          node_size=8)
    plan = rank_policies(StubModel(), topo_single(p=16, repl=2), prof,
                         micro_steps=2, prefetch=False)
    assert any(c.lossy_wire for c in plan.candidates)      # int8 in table
    assert not plan.chosen.lossy_wire                      # but not chosen
    assert not plan.chosen.lossy_hop2
    plan_h = rank_policies(StubModel(), topo_single(p=16, repl=2), prof,
                           micro_steps=2, prefetch=False,
                           allow_bf16_hop2=True)
    # hop-2 compression strictly reduces hop2 bytes: opted in, it wins
    assert plan_h.chosen.sync.hop2_wire_dtype == "bf16"
    # int8 wire halves gather bytes but its straight-through adjoint
    # reduce-scatters in fp32 (2x bf16), so in *training* it does not pay;
    # in serve mode (no gradients) it is the clear winner once allowed
    plan_s = rank_policies(StubModel(), topo_single(p=16, repl=2), prof,
                           mode="serve", prefetch=True, allow_int8=True)
    assert plan_s.chosen.gather.wire_dtype == "int8"


def test_candidate_grid_shape():
    cands = enumerate_candidates(topo_single(p=8, repl=2), prefetch=False)
    gathers = {(g.topology, g.wire_dtype, g.inner) for g, _ in cands}
    # flat + {inner,outer}x{2,4} per wire dtype, hop2 in {fp32, bf16,
    # int8}, hop1 in {fp32, int8} (the qgZ axis)
    assert len(gathers) == 3 * (1 + 2 * 2)
    assert {s.hop1_wire_dtype for _, s in cands} == {"fp32", "int8"}
    assert {s.hop2_wire_dtype for _, s in cands} == {"fp32", "bf16", "int8"}
    assert len(cands) == 3 * 2 * len(gathers)
    # p=2 degenerates to flat only
    flat_only = enumerate_candidates(
        StubTopo({"shard": 2, "repl": 1}, ("shard",), ("repl",)),
        prefetch=False)
    assert {g.topology for g, _ in flat_only} == {"flat"}
    # serving has no gradients: the hop-1 axis collapses
    serve = enumerate_candidates(topo_single(p=8, repl=2), prefetch=True,
                                 mode="serve")
    assert {s.hop1_wire_dtype for _, s in serve} == {"fp32"}


def test_plan_table_and_describe_serializable():
    plan = rank_policies(StubModel(), topo_single(p=8), "v5e",
                         micro_steps=2, prefetch=True)
    assert isinstance(plan, Plan)
    txt = plan.table()
    assert "autotune[v5e]" in txt and "*" in txt
    json.dumps(plan.describe())


def test_clip_and_carry_axes_ranked():
    """The approx-clip axis joins the ranked grid: approx rows on every
    bucketed train candidate (repl > 1) — and it does not outrank the
    reference numerics without its opt-in."""
    prof = custom_profile("axes-slow", intra_bw=100e9, inter_bw=1e9,
                          node_size=8)
    kw = dict(micro_steps=2, prefetch=True, hbm_budget_gb=64.0)
    plan = rank_policies(StubModel(), topo_single(p=8, repl=2), prof, **kw)
    assert {c.clip_mode for c in plan.candidates} == {"exact", "approx"}
    assert all(c.boundary == "bucketed" for c in plan.candidates
               if c.clip_mode == "approx")
    # pairing each bucketed candidate with its approx twin: pipelining
    # AdamW under hop-2 can only shrink the exposed time, and does shrink
    # it somewhere in the grid
    by_key = {}
    for c in plan.candidates:
        key = (c.gather, c.sync, c.boundary, c.hop2_bucket_mb)
        by_key.setdefault(key, {})[c.clip_mode] = c
    paired = [v for v in by_key.values() if len(v) == 2]
    assert paired
    for v in paired:
        assert v["approx"].t_hop2_exposed_s \
            <= v["exact"].t_hop2_exposed_s + 1e-18
    assert any(v["approx"].t_hop2_exposed_s < v["exact"].t_hop2_exposed_s
               for v in paired)
    # approx changes numerics: ranked, but chosen only under the opt-in
    assert plan.chosen.clip_mode == "exact"
    # the axis is a visible column in the ranked table
    txt = plan.table(top=None)
    head = txt.splitlines()[1]
    assert "clip" in head
    assert "approx" in txt


def test_resolve_roundtrips_clip_and_offload():
    """clip_mode='approx' on an auto config is the approximation opt-in;
    the resolved config carries the chosen clip and boundary fields and
    revalidates (approx only rides the bucket pipeline)."""
    prof = custom_profile("rt-axes", intra_bw=100e9, inter_bw=1e9,
                          node_size=8)
    mcfg = MiCSConfig(micro_steps=2, policy="auto", link_profile=prof,
                      clip_mode="approx", boundary_schedule="bucketed",
                      hbm_budget_gb=64.0)
    resolved, plan = resolve_config(mcfg, StubModel(),
                                    topo_single(p=8, repl=2))
    assert resolved.policy == "manual"
    assert resolved.clip_mode == plan.chosen.clip_mode
    assert resolved.boundary_schedule == plan.chosen.boundary
    if resolved.clip_mode == "approx":
        assert resolved.boundary_schedule == "bucketed"
    # an exact-clip config through the same grid never resolves to approx
    mcfg_e = dataclasses.replace(mcfg, clip_mode="exact")
    resolved_e, _ = resolve_config(mcfg_e, StubModel(),
                                   topo_single(p=8, repl=2))
    assert resolved_e.clip_mode == "exact"


# ---------------------------------------------------------------------------
# config resolution
# ---------------------------------------------------------------------------

def test_policy_field_validated():
    with pytest.raises(ValueError):
        MiCSConfig(policy="autotune")


def test_manual_config_passes_through():
    mcfg = MiCSConfig()
    resolved, plan = resolve_config(mcfg, StubModel(), topo_single())
    assert resolved is mcfg and plan is None


def test_resolve_roundtrips_through_from_config(topo1):
    """The resolved legacy fields must reconstruct exactly the chosen
    GatherPolicy/SyncPolicy when CommEngine.from_config interprets them."""
    prof = custom_profile("rt-slow", intra_bw=100e9, inter_bw=1e9,
                          node_size=8)
    mcfg = MiCSConfig(micro_steps=2, policy="auto", link_profile=prof,
                      prefetch=False)
    resolved, plan = resolve_config(mcfg, StubModel(),
                                    topo_single(p=16, repl=2))
    assert resolved.policy == "manual"
    eng = CommEngine.from_config(topo1, resolved)
    chosen = plan.chosen
    assert eng.gather_policy.topology == chosen.gather.topology
    assert eng.gather_policy.wire_dtype == chosen.gather.wire_dtype
    assert eng.gather_policy.inner == chosen.gather.inner
    assert eng.sync_policy == chosen.sync


# ---------------------------------------------------------------------------
# multi-device harness: analytical census == measured census
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def harness_results():
    return run_harness(HARNESS)


CHECKS = [
    "census_match_single", "census_match_prefetch", "census_match_multi",
    "census_match_qgz", "auto_plan_census",
]


@pytest.mark.parametrize("name", CHECKS)
def test_autotune_check(harness_results, name):
    res = harness_results.get(name)
    assert res is not None, f"harness did not run {name}"
    assert res["ok"], f"{name}: {res.get('err')}\n{res.get('tb', '')}"


def test_census_matrix_covered(harness_results):
    detail = harness_results.get("census_match_single_detail")
    assert detail is not None
    combos = {f"{t}/{w}" for t in ("flat", "inner_first", "outer_first")
              for w in ("fp32", "bf16", "int8")}
    assert combos <= set(detail)
    for combo, stages in detail.items():
        for stage, row in stages.items():
            assert abs(row["ratio"] - 1.0) <= 0.02, (combo, stage, row)
