"""The expert cell on the CPU at a tiny size: the program against the plain
reference (``reference/moe.py``), the expert share against the uncut layer,
dropless routing, the control against the cell's limits, and the FLOP
counter against the program's parameters."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell as cellmod
from chipbench import correct, harness
from chipbench.flops import moe as flops
from chipbench.program import moe as program
from chipbench.reference import moe as ref
from chipbench.tests import tiny_moe
from repro.core.mics import build_train_step
from repro.models import layers as L
from repro.models.blocks import moe_routed
from repro.models.build import build_model, exact_param_count


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


@pytest.mark.parametrize("chips,shard", [(1, 1), (4, 2)])
def test_reference_matches_float32_step(chips, shard):
    c = tiny_moe.cell(chips, shard)
    mine, checked, key = _program_readings(c, 2**33 + 5, f32=True)
    want = ref.readings(c.cfg, c.opt(), key, checked,
                        devices=jax.devices()[:chips])
    gaps = correct.gaps(mine, want)
    assert all(v < 2e-6 for v in gaps.values()), gaps


def test_control_reads_far_above_the_program():
    """The control (the reference in 8-bit floats), put in the program's
    place, fails the cell's limits on every seed and reads several times
    the program's gap."""
    limits = cellmod.load("dsv2lite_s4096_1chip").limits
    c = tiny_moe.cell()
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


# -- the expert layer alone --------------------------------------------------

def _layer_case(experts=64, top_k=6, d=32, f=16, n=64, norm=False, seed=0):
    """A reference expert layer (all ``experts`` held, 2 shared) and its
    tokens, in float32."""
    cfg = tiny_moe.config(
        hidden_size=d, moe_intermediate_size=f, n_routed_experts=experts,
        num_experts_per_tok=top_k, norm_topk_prob=norm,
        published={"num_hidden_layers": 3, "n_routed_experts": experts,
                   "vocab_size": 256})
    key = jax.random.key(seed)
    p = {k: ref.draw_flat(cfg, key, f"layers.1.{k}", int(np.prod(s))
                          ).reshape(s)
         for k, s in ref._layer_leaves(cfg, 1).items()
         if k in ("router", "ewg", "ewu", "ewd", "swg", "swu", "swd")}
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, n, d), jnp.float32)
    return cfg, p, x


@pytest.mark.parametrize("norm", [False, True])
def test_shares_add_up_to_the_uncut_layer(norm):
    """Eight shares of 64 experts (0-7, 8-15, ...), each the program's expert
    layer holding its 8, with the shared experts counted once, add up to the
    reference's whole layer."""
    cfg, p, x = _layer_case(norm=norm)
    shares = 8
    e_local = cfg["n_routed_experts"] // shares
    arch = dataclasses.replace(program.arch_config(cfg), experts_held=0)
    ctx = L.Ctx(tp=shares, tp_axis="model")
    split = lambda w: w.reshape(shares, e_local, *w.shape[1:])

    def share(wg, wu, wd):
        t = {"router.w": p["router"], "moe.wg": wg, "moe.wu": wu,
             "moe.wd": wd}
        return moe_routed(x[0], t, arch, ctx)

    parts, aux = jax.vmap(share, axis_name="model")(
        split(p["ewg"]), split(p["ewu"]), split(p["ewd"]))
    shared = L.mlp_swiglu(x[0], p["swg"], p["swu"], p["swd"])
    want, want_aux = ref._experts(cfg, ref.FP32, p, x, 1)
    np.testing.assert_allclose(np.asarray(parts.sum(0) + shared),
                               np.asarray(want[0]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(aux[:, 0]), float(want_aux),
                               rtol=1e-6)


def test_dropless_every_token_to_one_held_expert():
    """Every token's first choice is held expert 3: it gets them all (eight
    times an even share, far past any capacity), and none is lost."""
    cfg, p, x = _layer_case(experts=16, top_k=2, n=256)
    x = jnp.abs(x)
    p["router"] = p["router"].at[:, 3].set(1.0)
    held = 4
    t = {"router.w": p["router"], "moe.wg": p["ewg"][:held],
         "moe.wu": p["ewu"][:held], "moe.wd": p["ewd"][:held]}
    arch = dataclasses.replace(program.arch_config(cfg), experts_held=held)
    got, aux = moe_routed(x[0], t, arch, L.Ctx())
    want, _ = ref._experts({**cfg, "n_routed_experts": held}, ref.FP32,
                           {**p, "ewg": t["moe.wg"], "ewu": t["moe.wu"],
                            "ewd": t["moe.wd"]}, x, 1)
    shared = L.mlp_swiglu(x[0], p["swg"], p["swu"], p["swd"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0] - shared),
                               rtol=1e-5, atol=1e-6)
    probs = jax.nn.softmax(x[0] @ p["router"], -1)
    assert bool(jnp.all(jnp.argmax(probs, -1) == 3))
    # expert 3 takes all 256 first choices, at least twice the mean of the
    # held experts' loads even had every second choice been held
    assert float(aux[1]) >= 256 / (256 * 2 / held)


# -- FLOPs ---------------------------------------------------------------------

@pytest.mark.parametrize("which", ["cell", "tiny"])
def test_matmul_params_are_the_program_params_less_embedding(which):
    if which == "cell":
        with open(cellmod.HERE / "configs" / "deepseek-v2-lite-l5-e8.json") as f:
            cfg = json.load(f)
    else:
        cfg = tiny_moe.config()
    arch = program.arch_config(cfg)
    vectors = sum(s.size * pool.stack
                  for pool in build_model(arch, tp=1).all_pools()
                  for s in pool.layout.segments if len(s.shape) == 1)
    embedding = cfg["vocab_size"] * cfg["hidden_size"]
    assert flops.matmul_params(cfg) == (
        exact_param_count(arch) - embedding - vectors)
    assert cfg["d_model"] == cfg["hidden_size"]
    assert cfg["vocab"] == cfg["vocab_size"]


def test_cell_sizes_as_stated():
    """The parameters the configuration file's deployment states: 535.06M on
    the chip, 100.4M an expert layer, 81.0M the dense layer."""
    with open(cellmod.HERE / "configs" / "deepseek-v2-lite-l5-e8.json") as f:
        cfg = json.load(f)
    model = build_model(program.arch_config(cfg), tp=1)
    raw = {p.name: p.layout.raw_len for p in model.all_pools()}
    assert raw["dense"] == 81_007_104
    assert raw["layers"] == 100_405_760
    assert exact_param_count(program.arch_config(cfg)) == 535_060_992
    assert flops.expert_applications(cfg) == 0.75
