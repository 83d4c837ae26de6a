"""The declarative perf matrix: suites, cells, and the gates on each.

This is the single place the repo's bench surface is enumerated.  The
matrix runner (:mod:`repro.bench.runner`, CLI ``benchmarks/matrix.py``)
executes it end to end; each suite's thin ``--check`` shim evaluates just
its own slice through :func:`repro.bench.runner.check_suite` — so a
standalone ``benchmarks/comm_bench.py --smoke --check`` applies exactly
the gates declared here, and CI's single matrix invocation reproduces
every historical per-script gate.

Shared axis constants (policy labels, serve rates, figure names) live
here too: the suites import them, so a drift between "what the matrix
expects" and "what a suite emits" is a hard cell-missing failure, not a
silent coverage gap.
"""

from __future__ import annotations

from dataclasses import dataclass

from repro.bench.gates import GateSpec
from repro.bench.measure import config_hash


@dataclass(frozen=True)
class SuiteSpec:
    """One bench subprocess: script + per-mode args + wall-clock bound."""

    name: str
    script: str                   # repo-relative
    args: tuple = ()              # full-run extra argv
    smoke_args: tuple = ()        # smoke-run extra argv
    timeout_s: int = 1800

    def argv(self, smoke: bool) -> list:
        return list(self.smoke_args if smoke else self.args)

    def to_jsonable(self) -> dict:
        return {"script": self.script, "args": list(self.args),
                "smoke_args": list(self.smoke_args),
                "timeout_s": self.timeout_s}


@dataclass(frozen=True)
class CellSpec:
    """One declared matrix cell: the suite that must emit it + its gates."""

    id: str
    suite: str
    gates: tuple = ()

    def to_jsonable(self) -> dict:
        return {"suite": self.suite,
                "gates": [g.to_jsonable() for g in self.gates]}


@dataclass(frozen=True)
class MatrixSpec:
    suites: dict
    cells: dict
    smoke: bool

    def to_jsonable(self) -> dict:
        return {
            "smoke": self.smoke,
            "suites": {k: v.to_jsonable() for k, v in self.suites.items()},
            "cells": {k: v.to_jsonable() for k, v in self.cells.items()},
        }

    @property
    def config_hash(self) -> str:
        return config_hash(self.to_jsonable())


# ---------------------------------------------------------------------------
# shared axis constants (imported by the suites — drift becomes a hard
# cell-missing gate failure instead of silent coverage loss)
# ---------------------------------------------------------------------------

COMM_POLICY_LABELS = (
    "flat@bf16", "inner_first@bf16", "outer_first@bf16", "inner_first@int8",
    "inner_first@bf16+qgZ", "inner_first@int8+qgZ",
)
COMM_BOUNDARY_CELLS = ("serial", "bucketed", "bucketed_approx")
# step-time thresholds vs the same-run serial reference (the approx-clip
# cell's extra global-norm estimate can serialize badly when the
# 8-virtual-device host is contended — observed up to ~1.5x on an
# otherwise-passing host — so its bound is loose enough to only catch
# overlap actually breaking)
COMM_BOUNDARY_THRESHOLDS = {"bucketed": 1.2, "bucketed_approx": 1.8}

SERVE_RATES_FULL = ("0.25", "0.5", "1.0", "2.0", "inf")
SERVE_RATES_SMOKE = ("0.5", "inf")
SERVE_STEP_KINDS = ("fixed_decode", "paged_decode", "paged_chunk",
                    "fixed_prefill")
SERVE_PER_ROW_THRESHOLD = 1.2

MEMPLAN_CHECKS = ("footprint_match", "footprint_degenerate",
                  "remat_lowers_peak", "census_match_remat",
                  "carried_buffer_census")
ELASTIC_CHECKS = ("kill_pod_resume_bitwise", "grow_back_resume_bitwise",
                  "repick_keep_rule_bitwise", "resolve_scale_repick",
                  "data_continuity", "straggler_flagged", "crash_mid_save",
                  "reshard_roundtrip", "reshard_moments")
CHAOS_CHECKS = ("preempt_replay_bitwise", "grow_back_readmission",
                "straggler_evict", "crash_retry", "shed_under_burst")

# model-derived paper-figure cells: deterministic pure-model outputs, so
# the gate is EXACT value-hash reproducibility vs the baseline, not timing
FIGURE_CELLS = ("fig2", "fig7_8", "fig9", "fig10", "case_study_100b",
                "fig11", "fig12", "fig13", "fig14", "table1")
# full-run extras: real (CPU-training / model-building) cells whose floats
# are jax-version dependent — contract-gated on their internal asserts only
FIGURE_CELLS_FULL = ("fig15", "fig16")

# advisory ceiling for the checked-in-baseline timing comparison (only a
# hard gate on cells whose baseline entry sets "enforce": true)
BASELINE_TIMING_THRESHOLD = 1.5

_CONTRACT = (GateSpec(kind="contract"),)


def _timing_gates(reference: str, threshold: float,
                  normalize_by: str | None = None,
                  contract: bool = True) -> tuple:
    gates = [
        GateSpec(kind="ratio_vs_ref", reference=reference,
                 threshold=threshold, normalize_by=normalize_by),
        GateSpec(kind="ratio_vs_baseline",
                 threshold=BASELINE_TIMING_THRESHOLD,
                 normalize_by=normalize_by),
    ]
    if contract:
        gates.insert(0, GateSpec(kind="contract"))
    return tuple(gates)


def build_matrix(smoke: bool) -> MatrixSpec:
    """The full declarative matrix for one run mode."""
    suites = {
        "comm": SuiteSpec(
            "comm", "benchmarks/comm_bench.py",
            args=("--steps", "8"), smoke_args=("--smoke", "--steps", "5")),
        "serve": SuiteSpec(
            "serve", "benchmarks/serve_bench.py", smoke_args=("--smoke",)),
        "memplan": SuiteSpec("memplan", "tests/memplan_harness.py",
                             timeout_s=1500),
        "elastic": SuiteSpec("elastic", "tests/elastic_harness.py",
                             timeout_s=1500),
        "chaos": SuiteSpec("chaos", "tests/serve_chaos_harness.py",
                           timeout_s=1500),
        "figures": SuiteSpec(
            "figures", "benchmarks/run.py",
            args=("--matrix-cells", "--full"),
            smoke_args=("--matrix-cells",), timeout_s=900),
    }

    cells = {}

    def add(cid, suite, gates):
        cells[cid] = CellSpec(id=cid, suite=suite, gates=tuple(gates))

    # --- comm: gather schedules, policy ledger, boundary grid -------------
    add("comm/gather/serial", "comm", ())
    add("comm/gather/prefetch", "comm", _CONTRACT)   # loss bitwise equal
    for label in COMM_POLICY_LABELS:                 # census byte match
        add(f"comm/policy/{label}", "comm", _CONTRACT)
    for label in COMM_BOUNDARY_CELLS:
        if label == "serial":
            add("comm/boundary/serial", "comm", ())  # the in-run reference
        else:
            add(f"comm/boundary/{label}", "comm",
                _timing_gates("comm/boundary/serial",
                              COMM_BOUNDARY_THRESHOLDS[label]))
    add("comm/contract/predicted_exposed", "comm", _CONTRACT)

    # --- serve: interleaved step prices, closed-loop sweep, overload ------
    add("serve/step/fixed_decode", "serve", ())      # the in-run reference
    add("serve/step/paged_decode", "serve",
        _timing_gates("serve/step/fixed_decode", SERVE_PER_ROW_THRESHOLD,
                      normalize_by="rows", contract=False))
    add("serve/step/paged_chunk", "serve", ())
    add("serve/step/fixed_prefill", "serve", ())
    add("serve/equivalence", "serve", _CONTRACT)     # paged bitwise
    rates = SERVE_RATES_SMOKE if smoke else SERVE_RATES_FULL
    for rate in rates:
        gates = list(_CONTRACT)
        if rate == "inf":
            # paged beats the static baseline at saturation — a real
            # throughput claim, only trustworthy at full request counts
            gates.append(GateSpec(kind="metric_bound",
                                  metric="normalized_ratio", min_value=1.0,
                                  enforce_smoke=False))
        add(f"serve/rate/{rate}", "serve", gates)
    add("serve/overload", "serve", _CONTRACT)

    # --- the subprocess harnesses: contract matrices ----------------------
    for name in MEMPLAN_CHECKS:
        add(f"memplan/{name}", "memplan", _CONTRACT)
    for name in ELASTIC_CHECKS:
        add(f"elastic/{name}", "elastic", _CONTRACT)
    for name in CHAOS_CHECKS:
        add(f"chaos/{name}", "chaos", _CONTRACT)

    # --- paper figures: exact reproducibility, never timing ---------------
    for name in FIGURE_CELLS:
        add(f"figures/{name}", "figures",
            (GateSpec(kind="contract"),
             GateSpec(kind="exact_vs_baseline")))
    if not smoke:
        for name in FIGURE_CELLS_FULL:
            add(f"figures/{name}", "figures", _CONTRACT)

    return MatrixSpec(suites=suites, cells=cells, smoke=smoke)
