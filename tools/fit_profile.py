"""Measured-bandwidth profile calibration (ROADMAP: close the loop from
census to table).

``benchmarks/comm_bench.py`` emits, per gather policy, a ``fit_inputs``
ledger: the measured wall time per step plus the analytical per-stage
(tier, α-events, wire bytes) census of that policy's collectives.  Each
policy routes different byte/event mixes over the two link tiers, so the
set of policies over-determines the α-β model

    t_measured ≈ t0 + Σ_stages  alpha_events · α(tier) + wire_bytes / β(tier)

(``t0`` absorbs the policy-independent compute).  This tool least-squares
that system per tier and emits a ready-to-paste
``repro.core.linkmodel.custom_profile(...)`` snippet, turning a measured
``BENCH_comm.json`` from real hardware into a registered link table the
autotuner can rank policies over.

The ``host`` tier (device<->host DMA, core/linkmodel.py) joins the same
solve: a ledger whose stages carry ``tier='host'`` (one α-event per
transfer, point-to-point bytes) constrains the host (α, β) alongside the
network tiers.  Ledgers without such stages leave it unconstrained — the
snippet then omits ``host_bw`` and the profile falls back to
``DEFAULT_HOST_LINK``.

Usage:
  PYTHONPATH=src python tools/fit_profile.py artifacts/benchmarks/BENCH_comm.json \
      [--name fitted-cluster] [--node-size 8]

Caveats: on the CPU host meshes the "measured" times are compute-bound, so
the fitted bandwidths describe the host, not a network — the tool's value
is the mechanism, exercised on synthetic ledgers by
``tests/test_fit_profile.py`` and on real ledgers by running the bench on
a cluster.  Tiers that no observation exercises are reported as
unconstrained and filled from the ``--fallback`` profile.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import numpy as np

TIERS = ("intra", "inter", "host")

# Fit floors: α ≥ 0 s, bandwidth ≤ 10 TB/s (inv_bw floor).  Compute-bound
# ledgers can drive either coefficient negative; clamping keeps the emitted
# profile physical (and flags the clamp in the diagnostics).
ALPHA_FLOOR = 0.0
INV_BW_FLOOR = 1e-13


@dataclasses.dataclass(frozen=True)
class Observation:
    """One measured step: seconds + the per-stage ledger behind it."""

    label: str
    t_measured_s: float
    # stage label -> {"tier", "alpha_events", "wire_bytes"}
    stages: dict


@dataclasses.dataclass(frozen=True)
class TierFit:
    alpha: float                 # seconds per hop
    bandwidth: float             # bytes/second
    constrained: bool            # any observation exercised this tier
    clamped: bool                # fit hit a physical floor


@dataclasses.dataclass(frozen=True)
class FitResult:
    tiers: dict                  # tier name -> TierFit
    t0: float                    # policy-independent offset (seconds)
    residual_rms_s: float
    n_observations: int

    def describe(self) -> dict:
        return {
            "tiers": {k: dataclasses.asdict(v) for k, v in self.tiers.items()},
            "t0_s": self.t0,
            "residual_rms_s": self.residual_rms_s,
            "n_observations": self.n_observations,
        }


def observations_from_bench(bench: dict) -> list[Observation]:
    """Extract the fit ledger from a BENCH_comm.json ``policies`` section."""
    out = []
    for label, entry in bench.get("policies", {}).items():
        fi = entry.get("fit_inputs")
        if not fi:
            continue
        out.append(Observation(label=label,
                               t_measured_s=float(fi["t_measured_s"]),
                               stages=dict(fi["stages"])))
    return out


def _design(observations: list[Observation]):
    """Rows: one per observation.  Columns: [α per tier..., inv_bw per
    tier..., t0] in ``TIERS`` order (intra, inter, host)."""
    a = np.zeros((len(observations), 2 * len(TIERS) + 1))
    y = np.zeros(len(observations))
    for i, obs in enumerate(observations):
        y[i] = obs.t_measured_s
        a[i, -1] = 1.0
        for stage in obs.stages.values():
            j = TIERS.index(stage["tier"])
            a[i, j] += float(stage["alpha_events"])
            a[i, len(TIERS) + j] += float(stage["wire_bytes"])
    return a, y


def fit_tiers(observations: list[Observation]) -> FitResult:
    """Least-squares (α, β) per tier from measured step times.

    Columns no observation exercises are dropped from the solve (their tier
    is reported unconstrained); a rank check rejects underdetermined
    systems (fewer independent observations than exercised coefficients)
    instead of emitting an arbitrary min-norm answer; coefficients below
    the physical floors are clamped and refit is skipped — the residual
    then reports the clamp's cost honestly.
    """
    if len(observations) < 2:
        raise ValueError(
            f"need >= 2 observations to separate t0 from link terms, got "
            f"{len(observations)}")
    a, y = _design(observations)
    used = [j for j in range(a.shape[1])
            if j == a.shape[1] - 1 or np.any(a[:, j] != 0.0)]
    rank = np.linalg.matrix_rank(a[:, used])
    if rank < len(used):
        raise ValueError(
            f"underdetermined fit: {len(observations)} observations span "
            f"rank {rank} but {len(used)} coefficients are exercised — a "
            f"min-norm lstsq answer would be arbitrary.  Add policies with "
            f"different tier byte/event mixes to the bench ledger.")
    coef = np.zeros(a.shape[1])
    sol, *_ = np.linalg.lstsq(a[:, used], y, rcond=None)
    coef[used] = sol

    exercised = [
        bool(np.any(a[:, j] != 0.0) or np.any(a[:, len(TIERS) + j] != 0.0))
        for j in range(len(TIERS))
    ]
    clamped = [False] * len(TIERS)
    for j in range(len(TIERS)):
        if not exercised[j]:
            continue  # unconstrained, not degenerate — no floors to hit
        if coef[j] < ALPHA_FLOOR:
            coef[j], clamped[j] = ALPHA_FLOOR, True
        if coef[len(TIERS) + j] < INV_BW_FLOOR:
            coef[len(TIERS) + j], clamped[j] = INV_BW_FLOOR, True

    resid = y - a @ coef
    tiers = {}
    for j, name in enumerate(TIERS):
        constrained = exercised[j]
        inv = coef[len(TIERS) + j]
        tiers[name] = TierFit(
            alpha=float(coef[j]),
            bandwidth=float(1.0 / inv) if inv > 0 else float("inf"),
            constrained=bool(constrained),
            clamped=clamped[j],
        )
    return FitResult(
        tiers=tiers,
        t0=float(coef[-1]),
        residual_rms_s=float(np.sqrt(np.mean(resid ** 2))),
        n_observations=len(observations),
    )


def emit_snippet(fit: FitResult, *, name: str, node_size: int,
                 fallback: str = "v5e") -> str:
    """A ready-to-paste ``custom_profile(...)`` call for the fitted table.

    Unconstrained *network* tiers fall back to the named profile's values
    (flagged in the comment) so the snippet always constructs a valid
    LinkProfile; an unconstrained host tier is simply omitted —
    ``custom_profile`` then leaves ``host=None`` and the profile falls back
    to ``DEFAULT_HOST_LINK``.
    """
    from repro.core.linkmodel import get_profile

    fb = get_profile(fallback)
    vals = {}
    notes = []
    for tier in ("intra", "inter"):
        tf = fit.tiers[tier]
        if tf.constrained:
            vals[f"{tier}_bw"] = tf.bandwidth
            vals[f"alpha_{tier}"] = tf.alpha
            if tf.clamped:
                notes.append(f"{tier} tier hit a fit floor (clamped)")
        else:
            link = fb.link(tier)
            vals[f"{tier}_bw"] = link.bandwidth
            vals[f"alpha_{tier}"] = link.alpha
            notes.append(f"{tier} tier unconstrained; copied from "
                         f"{fallback!r}")
    host = fit.tiers["host"]
    if host.constrained:
        vals["host_bw"] = host.bandwidth
        vals["alpha_host"] = host.alpha
        if host.clamped:
            notes.append("host tier hit a fit floor (clamped)")
    else:
        notes.append("host tier unconstrained; DEFAULT_HOST_LINK applies")
    note = ("\n# NOTE: " + "; ".join(notes)) if notes else ""
    lines = [
        f"    {name!r},",
        f"    intra_bw={vals['intra_bw']:.6g},",
        f"    inter_bw={vals['inter_bw']:.6g},",
        f"    node_size={node_size},",
        f"    alpha_intra={vals['alpha_intra']:.6g},",
        f"    alpha_inter={vals['alpha_inter']:.6g},",
    ]
    if "host_bw" in vals:
        lines += [f"    host_bw={vals['host_bw']:.6g},",
                  f"    alpha_host={vals['alpha_host']:.6g},"]
    lines += ["    description='fitted from BENCH_comm.json',",
              "    register=True,"]
    body = "\n".join(lines)
    return (
        f"# fitted from {fit.n_observations} measured policies, "
        f"residual rms {fit.residual_rms_s:.3e} s{note}\n"
        f"from repro.core.linkmodel import custom_profile\n\n"
        f"profile = custom_profile(\n{body}\n)\n"
    )


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("bench", help="path to BENCH_comm.json")
    ap.add_argument("--name", default="fitted",
                    help="name for the emitted custom_profile")
    ap.add_argument("--node-size", type=int, default=8,
                    help="fast-tier island size of the measured cluster")
    ap.add_argument("--fallback", default="v5e",
                    help="profile supplying values for unconstrained tiers")
    args = ap.parse_args(argv)

    bench = json.loads(open(args.bench).read())
    obs = observations_from_bench(bench)
    if not obs:
        print("no fit_inputs ledgers in this BENCH_comm.json — re-run "
              "benchmarks/comm_bench.py", file=sys.stderr)
        return 1
    fit = fit_tiers(obs)
    print(json.dumps(fit.describe(), indent=1), file=sys.stderr)
    print(emit_snippet(fit, name=args.name, node_size=args.node_size,
                       fallback=args.fallback))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
