"""Unit tests for the measured-profile calibration tool
(tools/fit_profile.py): the per-tier (α, β) least-squares fit recovers a
known synthetic link table from policy-step observations, handles
unconstrained tiers via the fallback profile, and emits a runnable
``custom_profile()`` snippet."""

import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "tools"))

from fit_profile import (  # noqa: E402
    Observation, emit_snippet, fit_tiers, observations_from_bench,
)

# ground truth for the synthetic ledgers
ALPHA = {"intra": 2e-6, "inter": 20e-6, "host": 5e-6}
BW = {"intra": 40e9, "inter": 5e9, "host": 12e9}
T0 = 3e-3


def _obs(label, stages):
    t = T0
    for s in stages.values():
        t += s["alpha_events"] * ALPHA[s["tier"]] \
            + s["wire_bytes"] / BW[s["tier"]]
    return Observation(label=label, t_measured_s=t, stages=stages)


def _synthetic(n=6, tiers=("intra", "inter")):
    rng = np.random.default_rng(0)
    out = []
    for i in range(n):
        stages = {}
        for j, tier in enumerate(tiers):
            stages[f"stage{j}.{i}"] = {
                "tier": tier,
                "alpha_events": float(rng.integers(4, 200)),
                "wire_bytes": float(rng.integers(1, 2000)) * 1e6,
            }
        out.append(_obs(f"policy{i}", stages))
    return out


def test_fit_recovers_known_profile():
    fit = fit_tiers(_synthetic(8))
    for tier in ("intra", "inter"):
        tf = fit.tiers[tier]
        assert tf.constrained and not tf.clamped
        assert tf.alpha == pytest.approx(ALPHA[tier], rel=1e-4)
        assert tf.bandwidth == pytest.approx(BW[tier], rel=1e-4)
    assert fit.t0 == pytest.approx(T0, rel=1e-4)
    assert fit.residual_rms_s < 1e-9


def test_fit_unconstrained_tier_flagged():
    fit = fit_tiers(_synthetic(6, tiers=("intra",)))
    assert fit.tiers["intra"].constrained
    assert not fit.tiers["inter"].constrained
    assert fit.tiers["intra"].alpha == pytest.approx(ALPHA["intra"], rel=1e-4)


def test_fit_needs_two_observations():
    with pytest.raises(ValueError):
        fit_tiers(_synthetic(1))


def test_clamped_fit_stays_physical():
    """A compute-dominated ledger (comm terms swamped by noisy times) must
    not emit negative alphas or bandwidths."""
    rng = np.random.default_rng(3)
    obs = []
    for i in range(6):
        stages = {"s": {"tier": "intra", "alpha_events": 10.0 + 3 * i,
                        "wire_bytes": 1e6 * (1 + i % 3)}}
        obs.append(Observation(f"p{i}", 5e-3 + float(rng.normal(0, 1e-3)),
                               stages))
    fit = fit_tiers(obs)
    assert fit.tiers["intra"].alpha >= 0.0
    assert fit.tiers["intra"].bandwidth > 0.0


def test_underdetermined_fit_rejected():
    """Fewer independent observations than exercised coefficients must
    raise instead of emitting an arbitrary min-norm profile."""
    with pytest.raises(ValueError, match="underdetermined"):
        fit_tiers(_synthetic(4))           # 4 obs, 5 coefficients
    # collinear designs are rejected even with enough rows
    obs = [Observation(f"p{i}", 1e-3 * (1 + i % 2),
                       {"s": {"tier": "intra", "alpha_events": 10.0,
                              "wire_bytes": 1e6}})
           for i in range(6)]
    with pytest.raises(ValueError, match="underdetermined"):
        fit_tiers(obs)


def test_snippet_is_runnable_and_registers():
    fit = fit_tiers(_synthetic(8))
    code = emit_snippet(fit, name="fitted-test-table", node_size=4)
    ns: dict = {}
    exec(code, ns)  # the docs/CLI contract: ready-to-paste
    prof = ns["profile"]
    assert prof.intra.bandwidth == pytest.approx(BW["intra"], rel=1e-4)
    assert prof.inter.bandwidth == pytest.approx(BW["inter"], rel=1e-4)
    assert prof.node_size == 4
    from repro.core.linkmodel import get_profile

    assert get_profile("fitted-test-table") is prof


def test_snippet_fallback_for_unconstrained_tier():
    from repro.core.linkmodel import get_profile

    fit = fit_tiers(_synthetic(6, tiers=("intra",)))
    code = emit_snippet(fit, name="fitted-intra-only", node_size=8,
                        fallback="v5e")
    assert "unconstrained" in code
    ns: dict = {}
    exec(code, ns)
    assert ns["profile"].inter.bandwidth == get_profile("v5e").inter.bandwidth


def test_fit_recovers_host_tier():
    """A ledger with ``tier='host'`` stages constrains the device<->host
    (α, β) alongside the network tiers."""
    fit = fit_tiers(_synthetic(10, tiers=("intra", "inter", "host")))
    tf = fit.tiers["host"]
    assert tf.constrained and not tf.clamped
    assert tf.alpha == pytest.approx(ALPHA["host"], rel=1e-4)
    assert tf.bandwidth == pytest.approx(BW["host"], rel=1e-4)
    assert fit.residual_rms_s < 1e-9


def test_snippet_emits_host_tier_only_when_constrained():
    fit = fit_tiers(_synthetic(10, tiers=("intra", "inter", "host")))
    code = emit_snippet(fit, name="fitted-host-table", node_size=4)
    assert "host_bw" in code and "alpha_host" in code
    ns: dict = {}
    exec(code, ns)
    prof = ns["profile"]
    assert prof.link("host").bandwidth == pytest.approx(BW["host"], rel=1e-4)
    assert prof.link("host").alpha == pytest.approx(ALPHA["host"], rel=1e-4)
    # no host stages in the ledger -> the kwargs are omitted and the
    # profile falls back to DEFAULT_HOST_LINK
    from repro.core.linkmodel import DEFAULT_HOST_LINK

    code2 = emit_snippet(fit_tiers(_synthetic(8)), name="fitted-no-host",
                         node_size=4)
    assert "host_bw" not in code2
    ns2: dict = {}
    exec(code2, ns2)
    assert ns2["profile"].link("host") is DEFAULT_HOST_LINK


def test_observations_from_bench_shape():
    bench = {"policies": {
        "flat@bf16": {"fit_inputs": {
            "t_measured_s": 1e-3,
            "stages": {"param_gather.flat": {
                "tier": "intra", "alpha_events": 6.0, "wire_bytes": 1e6}},
        }},
        "no-ledger": {},
    }}
    obs = observations_from_bench(bench)
    assert len(obs) == 1 and obs[0].label == "flat@bf16"
    assert obs[0].stages["param_gather.flat"]["tier"] == "intra"
