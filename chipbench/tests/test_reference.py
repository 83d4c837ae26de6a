"""The plain reference against the program's training step, on the CPU at
a tiny size: with the step computing in float32 the two agree to float32
rounding, on one device and on a repl=2 x shard=2 mesh; in the program's
bfloat16 the control (the reference in 8-bit floats), put in the
program's place, fails the cells' limits and reads several times the
program's gap."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench import cell as cellmod
from chipbench import correct, harness
from chipbench.reference import dense as ref
from chipbench.tests import tiny
from repro.core.mics import build_train_step


def _program_readings(c, seed, f32: bool):
    p = c.module("program").Program(c.cfg, c.traffic, c.opt())
    if f32:
        p.step = build_train_step(
            p.model, p.topo,
            dataclasses.replace(p.mcfg, gather_dtype=jnp.float32), p.oc)
    checked, _ = harness.batches(c, seed)
    key = harness.seed_key(seed)
    state, compiled, *_ = harness.start(p, seed, key, checked[0])
    feed = harness.Feed(compiled, state)
    mine, _ = harness.checked_steps(p, feed, key, checked)
    return mine, checked, key


@pytest.mark.parametrize("kind,chips,shard", [
    ("bert", 1, 1), ("yi", 1, 1), ("yi", 4, 2)])
def test_reference_matches_float32_step(kind, chips, shard):
    c = tiny.cell(kind, chips, shard)
    mine, checked, key = _program_readings(c, 2**33 + 5, f32=True)
    want = ref.readings(c.cfg, c.opt(), key, checked,
                        devices=jax.devices()[:chips])
    gaps = correct.gaps(mine, want)
    assert all(v < 2e-6 for v in gaps.values()), gaps


@pytest.mark.parametrize("kind,workload", [("bert", "bert10b_s512_1chip"),
                                           ("yi", "yi9b_s4096_1chip")])
def test_control_reads_far_above_the_program(kind, workload):
    """The control, put in the program's place, comes out not correct at
    the cell's own limits on every seed, and reads several times the
    program's gap."""
    limits = cellmod.load(workload).limits
    c = tiny.cell(kind)
    prog, ctl = [], []
    for seed in (1, 2, 3):
        mine, checked, key = _program_readings(c, seed, f32=False)
        want = ref.readings(c.cfg, c.opt(), key, checked)
        prog.append(correct.gaps(mine, want))
        ctl.append(correct.gaps(
            ref.readings(c.cfg, c.opt(), key, checked, ref.FP8), want))
        assert not correct.passed(correct.checks(ctl[-1], limits)), ctl[-1]
    worst = {k: max(g[k] for g in prog) for k in correct.NUMBERS}
    least = {k: min(g[k] for g in ctl) for k in correct.NUMBERS}
    assert least["grad_gap"] > 3 * worst["grad_gap"], (worst, least)
