"""A run with the timed path broken underneath comes out not correct.

Each test drives ``harness.run`` (past its look for a chip) on a tiny cell
on the CPU, with the program's step wrapped to plant one fault: the state
returned unchanged, half of each micro-batch left out (the mean taken over
the rest), or, on the repl=2 x shard=2 mesh, the exchange between replicas
(hop 2) left out.  The limits are the tiny cells' own: the sound runs pass
them on these seeds with room.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests import tiny

LIMITS = {"loss_gap": 3e-3, "grad_gap": 1e-2, "update_gap": 5e-3}


def _run(c, fault=None, seed=2**31 + 11):
    return harness.run(c, seed, 0.5, False, t0=time.time(), peak=1e12,
                       fault=fault)


def unchanged(compiled):
    def step(state, batch):
        _, metrics = compiled(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return step


def half_batch(compiled):
    def step(state, batch):
        rows = batch["mask"].shape[1]
        batch = {**batch, "mask": batch["mask"].at[:, rows // 2:].set(0.0)}
        return compiled(state, batch)
    return step


@pytest.mark.parametrize("chips,shard", [(1, 1), (4, 2)])
def test_sound_run_is_correct(chips, shard):
    line = _run(tiny.cell("bert", chips, shard, LIMITS))
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_fault_is_not_correct(fault):
    line = _run(tiny.cell("bert", limits=LIMITS), fault)
    assert not line["correct"], line["checks"]


def test_no_exchange_between_replicas_is_not_correct(monkeypatch):
    from repro.core import collectives

    monkeypatch.setattr(collectives, "hop2_all_reduce", lambda g, topo: g)
    line = _run(tiny.cell("bert", 4, 2, LIMITS))
    assert not line["correct"], line["checks"]
