"""The train step names its device work by layer (``repro.scopes``).

Each scope reaches the compiled step's ``op_name`` metadata, every
collective of the step lies under a communicating scope, and the lowered
module with debug info stripped, which is what the compilation cache keys
on by default, names none of them.
"""

import pathlib
import re

import pytest
from harness_util import run_harness
from scopes_harness import innermost_scope, instructions, lower_step

from repro import scopes

# what each layer puts under its scope on one device: nothing crosses a
# wire, and at p=1 the compiler drops hop 1's lone barrier
ONE_DEVICE = set(scopes.ALL) - {scopes.HOP1, scopes.HOP2}
COMM = {scopes.GATHER, scopes.HOP1, scopes.HOP2, scopes.OPTIMIZER}


@pytest.fixture(scope="module", params=["bert-10b", "yi-9b", "deepseek-v2-lite"])
def one_device(request):
    lowered = lower_step(request.param)
    return lowered, instructions(lowered.compile().as_text())


@pytest.fixture(scope="module")
def mesh():
    return run_harness(pathlib.Path(__file__).parent / "scopes_harness.py")


def test_scopes_reach_the_compiled_step(one_device):
    _, ins = one_device
    found = {innermost_scope(op) for _, _, op in ins} - {None}
    assert ONE_DEVICE <= found, ONE_DEVICE - found


def test_stripped_module_names_no_scope(one_device):
    lowered, _ = one_device
    stripped = lowered.as_text(debug_info=False)
    assert [s for s in scopes.ALL if s in stripped] == []
    assert any(s in lowered.as_text(debug_info=True) for s in scopes.ALL)


def test_mesh_step_carries_every_scope(mesh):
    assert sorted(scopes.ALL) == mesh["scopes"]


def test_mesh_collectives_lie_under_comm_scopes(mesh):
    kinds = {k for _, k, _ in mesh["collectives"]}
    assert {"all-gather", "reduce-scatter", "all-reduce"} <= kinds, kinds
    outside = [(n, op) for n, _, op in mesh["collectives"]
               if innermost_scope(op) not in COMM]
    assert outside == [], outside


def test_mesh_stripped_module_names_no_scope(mesh):
    assert mesh["stripped_names"] == []


def test_expert_scopes_lie_inside_mlp(one_device, request):
    """In an expert layer the routing and the grouped products carry their
    own scopes, nested in ``model.mlp``: a reader of ``scopes.ALL`` still
    puts them under the MLP."""
    _, ins = one_device
    ops = [op for _, _, op in ins if op]
    for s in scopes.MOE:
        under = [op for op in ops if s in op]
        if "deepseek" in request.node.callspec.params["one_device"]:
            assert under, s
        assert all(re.search(rf"{re.escape(scopes.MLP)}/(.*/)?{re.escape(s)}",
                             op) for op in under), s
        assert {innermost_scope(op) for op in under} <= {scopes.MLP}


def test_stripped_module_names_no_expert_scope(one_device):
    lowered, _ = one_device
    stripped = lowered.as_text(debug_info=False)
    assert [s for s in scopes.MOE if s in stripped] == []
