"""Memory-planner tests.

Device-free units exercise the footprint algebra, the paper's §3.1
minimal-partition-group rule (``min_partition_size`` / ``resolve_scale``)
and the autotuner's ``hbm_budget_gb`` gate over duck-typed stubs; the
predicted-vs-compiled property runs through the 8-virtual-device subprocess
harness (tests/memplan_harness.py), which is also the CI smoke gate.

Degenerate cases covered per the ISSUE: a single-device mesh, a partition
group spanning the whole world, the remat carry's bitwise-equal losses vs
the stored carry (harness), and a budget smaller than any candidate
(a clear :class:`MemoryBudgetError`, never a silent empty plan).
"""

import dataclasses
import pathlib

import pytest

from harness_util import run_harness, stored_carry
from repro.core import memplan as M
from repro.core.autotune import rank_policies, resolve_config, resolve_scale
from repro.core.comm import GatherPolicy, SyncPolicy
from repro.core.linkmodel import GIB
from repro.core.memplan import (
    DeviceGrid, MemoryBudgetError, min_partition_size,
    partition_size_candidates, predict_footprint,
)
from repro.core.mics import MiCSConfig

HARNESS = pathlib.Path(__file__).parent / "memplan_harness.py"


# ---------------------------------------------------------------------------
# device-free stubs (same duck-typing contract as test_autotune.py)
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
    return StubTopo({"shard": p, "repl": repl}, ("shard",), ("repl",))


# ---------------------------------------------------------------------------
# footprint algebra
# ---------------------------------------------------------------------------

def test_footprint_components_and_ordering():
    model = StubModel()
    gp = GatherPolicy(prefetch=True)
    gp_serial = GatherPolicy(prefetch=False)
    sp = SyncPolicy()
    grid = DeviceGrid(partition_size=4, replication_degree=2)
    with stored_carry():
        stored = predict_footprint(model, grid, gp, sp, micro_steps=2)
    plans = {
        "stored": stored,
        "remat": predict_footprint(model, grid, gp, sp, micro_steps=2),
        "serial": predict_footprint(model, grid, gp_serial, sp,
                                    micro_steps=2),
    }
    # the carry ordering the planner exists to price
    assert plans["stored"].total_bytes > plans["remat"].total_bytes \
        > plans["serial"].total_bytes
    # states are identical (they do not depend on the schedule)
    assert len({p.args_bytes for p in plans.values()}) == 1
    comp = plans["stored"].components
    for key in ("gather_buffers", "grad_accum", "boundary_reduced",
                "prefetch_carry", "hop2_staging"):
        assert comp[key] > 0, (key, comp)
    assert "prefetch_carry" not in plans["serial"].components
    # remat's carry is the O(layers x shard) term: well below stored's
    # O(layers x flat_len) (the gap widens with p — at p=4 it is ~4x)
    assert plans["remat"].components["prefetch_carry"] \
        < plans["stored"].components["prefetch_carry"] / 2


def test_footprint_scales_with_partition_size():
    """Doubling p halves the sharded states but not the gathered buffers —
    the exact trade the paper's minimal-group rule walks."""
    model, sp = StubModel(), SyncPolicy()
    gp = GatherPolicy(prefetch=True)
    p2 = predict_footprint(model, DeviceGrid(2, 8), gp, sp)
    p8 = predict_footprint(model, DeviceGrid(8, 2), gp, sp)
    assert p8.args_bytes < p2.args_bytes
    assert p8.components["gather_buffers"] == p2.components["gather_buffers"]


def test_footprint_degenerate_grids():
    model, sp = StubModel(), SyncPolicy()
    gp = GatherPolicy(wire_dtype="int8", prefetch=True)
    # single device: nothing on the wire -> no quant scratch, no hop-2
    one = predict_footprint(model, DeviceGrid(1, 1), gp,
                            SyncPolicy(hop1_wire_dtype="int8"))
    assert "int8_wire_scratch" not in one.components
    assert "qgz_scratch" not in one.components
    assert "hop2_staging" not in one.components
    # partition group == world: no replication -> no hop-2 staging
    world = predict_footprint(model, DeviceGrid(16, 1), gp, sp)
    assert "hop2_staging" not in world.components
    assert "int8_wire_scratch" in world.components


def test_footprint_encdec_decoder_pools_price_stored_carry():
    """models/lm.py routes enc-dec *decoder* pools to the stored carry (a
    custom VJP may not close over the gradient-carrying encoder output);
    the planner must price them as stored so the budget gate never
    under-predicts."""
    class EncDecModel:
        class cfg:  # noqa: D106 - duck-typed ArchConfig surface
            family = "encdec"
            d_model = 64
            vocab = 256

        def __init__(self):
            self.pools = (StubPool("enc_layers"), StubPool("dec_layers"))
            self._shapes = {
                "embed": (1, 1, 16384),
                "enc_layers": (4, 1, 65536),
                "dec_layers": (4, 1, 65536),
                "head": (1, 1, 20480),
            }

        def all_pools(self):
            return (StubPool("embed"), StubPool("enc_layers"),
                    StubPool("dec_layers"), StubPool("head"))

        def global_flat_shapes(self):
            return dict(self._shapes)

    grid, sp = DeviceGrid(4, 2), SyncPolicy()
    with stored_carry():
        stored = predict_footprint(EncDecModel(), grid,
                                   GatherPolicy(prefetch=True), sp)
    remat = predict_footprint(EncDecModel(), grid,
                              GatherPolicy(prefetch=True), sp)
    s_carry = stored.components["prefetch_carry"]
    r_carry = remat.components["prefetch_carry"]
    # remat only relieves the encoder pool; the decoder half stays stored
    assert s_carry / 2 < r_carry < s_carry


def test_footprint_activation_terms_need_shapes():
    class CfgModel(StubModel):
        class cfg:  # noqa: D106 - duck-typed ArchConfig surface
            d_model = 64
            vocab = 256
        tp = 1
        vocab_padded = 256

    sp = SyncPolicy()
    gp = GatherPolicy(prefetch=True)
    bare = predict_footprint(CfgModel(), DeviceGrid(4, 2), gp, sp)
    sized = predict_footprint(CfgModel(), DeviceGrid(4, 2), gp, sp,
                              local_batch=2, seq=128)
    assert "activation_ckpt" not in bare.components
    assert sized.components["activation_ckpt"] > 0
    assert sized.components["logits_ce"] > 0
    assert sized.args_bytes > bare.args_bytes  # the batch itself


# ---------------------------------------------------------------------------
# the §3.1 rule: minimal partition group that fits
# ---------------------------------------------------------------------------

def test_partition_size_candidates():
    assert partition_size_candidates(16) == [1, 2, 4, 8, 16]
    assert partition_size_candidates(12) == [1, 2, 3, 4, 6, 12]
    with pytest.raises(ValueError):
        partition_size_candidates(0)


def test_min_partition_size_picks_minimal():
    model = StubModel()
    # p=1 needs ~3x full states; find a budget that p=4 just satisfies
    need = {p: predict_footprint(
        model, DeviceGrid(p, 16 // p), GatherPolicy(prefetch=True),
        SyncPolicy()).total_bytes for p in (1, 2, 4, 8, 16)}
    budget_gb = (need[4] + 1) / GIB
    assert need[2] > need[4] + 1  # the budget really excludes p=2
    p, carry, plan = min_partition_size(
        model, data_extent=16, hbm_budget_gb=budget_gb)
    assert p == 4 and carry == "remat"
    assert plan.total_bytes <= budget_gb * GIB


def test_min_partition_size_remat_rescues_smaller_group():
    """A budget between a group's remat and stored footprints must pick the
    SMALLER group with remat, not grow the group — smaller groups keep
    collectives on faster tiers, the whole point of scale-aware
    partitioning."""
    model = StubModel()
    gp = GatherPolicy(prefetch=True)
    sp = SyncPolicy()
    with stored_carry():
        stored4 = predict_footprint(
            model, DeviceGrid(4, 4), gp, sp).total_bytes
        p_stored_only, _, _ = min_partition_size(
            model, data_extent=16, hbm_budget_gb=(stored4 - 1) / GIB)
    remat4 = predict_footprint(model, DeviceGrid(4, 4), gp, sp).total_bytes
    assert remat4 < stored4
    budget_gb = (remat4 + stored4) / 2 / GIB
    p, carry, _plan = min_partition_size(
        model, data_extent=16, hbm_budget_gb=budget_gb)
    assert (p, carry) == (4, "remat")
    assert p_stored_only > p


def test_min_partition_size_budget_too_small_is_clear_error():
    with pytest.raises(MemoryBudgetError) as ei:
        min_partition_size(StubModel(), data_extent=16,
                           hbm_budget_gb=1e-6)
    msg = str(ei.value)
    assert "no partition group fits" in msg
    assert "GiB per device" in msg


# ---------------------------------------------------------------------------
# autotuner integration: the hbm_budget_gb gate
# ---------------------------------------------------------------------------

def test_rank_policies_prices_memory():
    plan = rank_policies(StubModel(), topo_single(p=4, repl=2), "v5e",
                         micro_steps=2)
    assert all(c.mem_bytes > 0 for c in plan.candidates)
    assert "mem_GB" in plan.table()
    assert plan.hbm_budget_gb is None
    # without a budget every row re-gathers (no host-offloaded rows)
    assert {c.gather.carry_offload for c in plan.candidates} == {"none"}


def test_rank_policies_budget_filters_and_falls_back_to_remat():
    model, topo = StubModel(), topo_single(p=4, repl=2)
    with stored_carry():
        stored_best = rank_policies(model, topo, "v5e", micro_steps=2).chosen
    # a budget below the stored footprint but above remat's: remat is
    # slower (one extra gather per layer) but fits
    remat_plan = rank_policies(model, topo, "v5e", micro_steps=2,
                               hbm_budget_gb=1e6)  # effectively unlimited
    remat_rows = [c for c in remat_plan.candidates
                  if c.gather.carry_offload == "none"]
    assert remat_rows, "budgeted ranking must include the remat rows"
    budget_gb = (min(c.mem_bytes for c in remat_rows) + 1) / GIB
    gated = rank_policies(model, topo, "v5e", micro_steps=2,
                          hbm_budget_gb=budget_gb)
    assert gated.chosen.gather.carry_offload == "none"
    assert gated.chosen.mem_bytes <= budget_gb * GIB
    assert stored_best.mem_bytes > budget_gb * GIB
    assert gated.chosen.t_comm_s >= stored_best.t_comm_s


def test_rank_policies_budget_too_small_raises():
    with pytest.raises(MemoryBudgetError):
        rank_policies(StubModel(), topo_single(p=4, repl=2), "v5e",
                      micro_steps=2, hbm_budget_gb=1e-6)


def test_resolve_config_applies_budget(topo1):
    model, topo = StubModel(), topo_single(p=4, repl=2)
    remat_plan = rank_policies(model, topo, "v5e", micro_steps=2,
                               hbm_budget_gb=1e6)
    remat_rows = [c for c in remat_plan.candidates
                  if c.gather.carry_offload == "none"]
    budget_gb = (min(c.mem_bytes for c in remat_rows) + 1) / GIB
    mcfg = MiCSConfig(micro_steps=2, policy="auto", link_profile="v5e",
                      hbm_budget_gb=budget_gb)
    resolved, plan = resolve_config(mcfg, model, topo)
    assert plan.hbm_budget_gb == budget_gb
    assert resolved.carry_offload == "none"
    # and the resolved config reconstructs the chosen policy end to end
    from repro.core.comm import CommEngine

    eng = CommEngine.from_config(topo1, resolved)
    assert eng.gather_policy.carry_offload == "none"


def test_resolve_scale_minimal_group():
    model = StubModel()
    need4 = predict_footprint(
        model, DeviceGrid(4, 4), GatherPolicy(prefetch=True),
        SyncPolicy()).total_bytes
    mcfg = MiCSConfig(micro_steps=1, hbm_budget_gb=(need4 + 1) / GIB)
    p, carry, plan = resolve_scale(model, mcfg, data_extent=16)
    assert p == 4 and carry == "remat"
    with pytest.raises(ValueError):
        resolve_scale(model, MiCSConfig(), data_extent=16)
    with pytest.raises(MemoryBudgetError):
        resolve_scale(model, dataclasses.replace(mcfg, hbm_budget_gb=1e-6),
                      data_extent=16)


def test_config_validation():
    with pytest.raises(ValueError):
        MiCSConfig(carry_offload="offload")
    with pytest.raises(ValueError):
        MiCSConfig(hbm_budget_gb=0.0)
    with pytest.raises(ValueError):
        GatherPolicy(carry_offload="nvme")


# ---------------------------------------------------------------------------
# multi-device harness: predicted footprint == compiled memory analysis
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def harness_results():
    return run_harness(HARNESS)


CHECKS = [
    "footprint_match", "footprint_degenerate", "remat_lowers_peak",
    "census_match_remat", "carried_buffer_census", "offload_lowers_peak",
]


@pytest.mark.parametrize("name", CHECKS)
def test_memplan_check(harness_results, name):
    res = harness_results.get(name)
    assert res is not None, f"harness did not run {name}"
    assert res["ok"], f"{name}: {res.get('err')}\n{res.get('tb', '')}"


def test_footprint_matrix_covered(harness_results):
    detail = harness_results.get("footprint_match_detail")
    assert detail is not None
    combos = {f"{t}/{c}" for t in ("flat", "inner_first", "outer_first")
              for c in ("stored", "remat")}
    assert combos <= set(detail)
    for combo, row in detail.items():
        assert row["predicted_args_bytes"] == row["measured_args_bytes"]
        assert abs(row["temp_ratio"] - 1.0) <= M.MEM_RTOL, (combo, row)


def test_remat_saving_is_the_carry(harness_results):
    """The compiled stored-vs-remat temp delta is dominated by the carry
    component the planner prices."""
    saving = harness_results["remat_lowers_peak_detail"]["saving_bytes"]
    det = harness_results["footprint_match_detail"]
    pred_delta = (det["inner_first/stored"]["components"]["prefetch_carry"]
                  - det["inner_first/remat"]["components"]["prefetch_carry"])
    assert saving > 0
    assert abs(pred_delta - saving) <= 0.5 * saving


def test_offload_peak_accounting(harness_results):
    """carry_offload='host' + offload_opt shrink the compiled peak the way
    the planner predicts: temps lose the carry residual, args lose the
    fp32 m/v shards (2/3 of the 3x-fp32 state), args stay exact."""
    det = harness_results["offload_lowers_peak_detail"]
    s, hc, ho = det["stored"], det["host_carry"], det["host_carry_opt"]
    for row in (s, hc, ho):
        assert row["predicted_args_bytes"] == row["measured_args_bytes"]
    assert hc["measured_temp_bytes"] < s["measured_temp_bytes"]
    # m/v leave the donated args: the drop is ~2/3 of the state bytes
    drop = s["measured_args_bytes"] - ho["measured_args_bytes"]
    assert drop > 0.5 * s["measured_args_bytes"], det


def test_expert_model_pools_are_priced_and_routed():
    """DeepSeek-V2-Lite's smoke variant: a dense pool (one layer, serial)
    and an expert pool (remat), the expert layer's dropless row buffers
    priced, the remat carry only its one in-flight buffer."""
    from repro.configs import get_config, smoke_variant
    from repro.models.build import build_model
    from repro.models.lm import train_routes

    model = build_model(smoke_variant(get_config("deepseek-v2-lite")), tp=1)
    gp = GatherPolicy(prefetch=True)
    assert train_routes(model, gp) == {"dense": "serial", "layers": "remat"}
    grid = DeviceGrid(partition_size=1, replication_degree=1)
    plan = predict_footprint(model, grid, gp, SyncPolicy(), micro_steps=2,
                             local_batch=2, seq=32)
    cfg = model.cfg
    assert plan.components["expert_rows"] == (
        2 * 32 * cfg.top_k * (2 * cfg.d_model + 3 * cfg.d_ff) * 2)
    flat = model.pool("layers").layout.flat_len
    assert plan.components["prefetch_carry"] == flat * 2
