"""The dense FLOP counter against the program's own counts."""

import json

import jax.numpy as jnp
import pytest

from chipbench import cell as cellmod
from chipbench import harness
from chipbench.flops import dense as flops
from chipbench.program import dense as program
from chipbench.tests import tiny
from repro.models.build import build_model, exact_param_count


def _config(name):
    with open(cellmod.HERE / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["bert-10b-l4", "yi-9b-l1-v16k"])
def test_matmul_params_are_the_program_params_less_embedding(name):
    cfg = _config(name)
    arch = program.arch_config(cfg)
    vectors = sum(s.size * pool.stack
                  for pool in build_model(arch, tp=1).all_pools()
                  for s in pool.layout.segments if len(s.shape) == 1)
    embedding = cfg["vocab"] * cfg["d_model"]
    assert flops.matmul_params(cfg) == (
        exact_param_count(arch) - embedding - vectors)


@pytest.mark.parametrize("kind", ["bert", "yi"])
def test_counter_sits_under_the_compiled_step_by_the_recomputed_forward(
        kind):
    from repro.roofline import hlo_stats

    c = tiny.cell(kind)
    p = c.module("program").Program(c.cfg, c.traffic, c.opt())
    checked, _ = harness.batches(c, 1)
    state = p.init_state(1)
    text = p.step.lower(state, {k: jnp.asarray(v) for k, v in
                                checked[0].items()}).compile().as_text()
    mesh = dict(zip(p.topo.mesh.axis_names, p.topo.mesh.devices.shape))
    hlo = hlo_stats.analyze(text, mesh)["dot_flops"]
    cfg, seq, tokens = c.cfg, c.traffic["seq"], c.tokens_per_step
    model = tokens * flops.flops_per_token(cfg, seq)
    # Each layer's forward runs again under jax.checkpoint, all but the
    # MLP's down projection, whose output the backward pass never reads;
    # the head is outside the checkpoint.
    L, d = cfg["n_layers"], cfg["d_model"]
    layers = flops.matmul_params(cfg) - d * cfg["vocab"] - L * d * cfg["d_ff"]
    width = cfg["n_heads"] * cfg["head_dim"]
    recomputed = tokens * (2 * layers + 4 * L * width * seq)
    assert model < hlo
    assert hlo == pytest.approx(model + recomputed, rel=1e-9)
