"""Runs the 8-virtual-device correctness harness in a subprocess (keeps this
process at 1 device) and asserts every named check passed.  Covers:
hierarchical gather correctness+gradients, MiCS==single-device fidelity
(paper Fig 16), ZeRO-3 equivalence, the Fig-14 alternative schedule,
hierarchical-training equivalence, compressed hop-2, decode consistency."""

import pathlib

import pytest

from harness_util import run_harness

HARNESS = pathlib.Path(__file__).parent / "dist_harness.py"


@pytest.fixture(scope="module")
def harness_results():
    return run_harness(HARNESS)


CHECKS = [
    "hier_gather", "mics_fidelity", "zero3_equiv", "alt_sync_equiv",
    "hier_train_equiv", "compress_hop2", "moe_tp_equiv", "mla_tp_equiv",
    "griffin_partition_equiv", "mlstm_chunk_train_equiv",
    "decode_consistency", "init_state_placement",
]


@pytest.mark.parametrize("name", CHECKS)
def test_distributed_check(harness_results, name):
    res = harness_results.get(name)
    assert res is not None, f"harness did not run {name}"
    assert res["ok"], f"{name}: {res.get('err')}\n{res.get('tb', '')}"
