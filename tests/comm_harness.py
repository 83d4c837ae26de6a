"""CommEngine correctness harness, run in a subprocess with 8 virtual CPU
devices (same pattern as dist_harness.py).  Prints one JSON object with named
check results; tests/test_comm.py asserts on them.  Checks:

  policy_equiv       flat / inner_first / outer_first gather policies produce
                     bitwise-identical full buffers, on single- and
                     multi-axis partition groups
  vjp_matches_rs     every policy's VJP equals the explicit
                     hop1_reduce_scatter of the upstream cotangent
  int8_wire_gather   ZeRO++-style int8 wire gathers stay within the blockwise
                     quantization error bound and still train (grads flow
                     through the straight-through adjoint)
  prefetch_bitwise   double-buffered prefetch training losses are *bitwise*
                     equal to the serial schedule's
  prefetch_decode    prefill+decode logits bitwise equal across schedules
  prefetch_census    compiled HLO of the prefetch schedule shows all-gathers
                     carried into the layer-scan loop carry (issued one layer
                     ahead); the serial schedule shows none
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import json
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, smoke_variant
from repro.core import collectives as C
from repro.core.comm import CommEngine, GatherPolicy, SyncPolicy
from repro.core.mics import (
    MiCSConfig, build_train_step, init_state, init_state_shapes,
    make_batch_shapes,
)
from repro.core.topology import MiCSTopology, make_host_mesh
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.roofline.hlo_stats import analyze

RESULTS = {}

POLICIES = ("flat", "inner_first", "outer_first")


def check(name):
    def deco(fn):
        try:
            fn()
            RESULTS[name] = {"ok": True}
        except Exception as e:  # noqa: BLE001
            RESULTS[name] = {
                "ok": False,
                "err": f"{type(e).__name__}: {e}",
                "tb": traceback.format_exc()[-2000:],
            }
        return fn
    return deco


def _topos():
    """(label, topo, in_spec) for single- and multi-axis partition groups."""
    single = MiCSTopology(make_host_mesh(1, 2, 4, 1),
                          partition_axes=("shard",),
                          replication_axes=("pod", "repl"))
    multi = MiCSTopology(make_host_mesh(2, 1, 4, 1),
                         partition_axes=("pod", "shard"),
                         replication_axes=("repl",))
    return [("single", single, P("shard", None)),
            ("multi", multi, P(("pod", "shard"), None))]


def _engine(topo, policy, **kw):
    gp = GatherPolicy(topology=policy, wire_dtype=kw.pop("wire", "fp32"),
                      prefetch=kw.pop("prefetch", False),
                      inner=kw.pop("inner", None))
    return CommEngine(topo, gp, SyncPolicy(**kw))


# ---------------------------------------------------------------------------
@check("policy_equiv")
def _policy_equiv():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(32, 4)), jnp.float32)
    for label, topo, in_spec in _topos():
        mesh = topo.mesh

        def run(engine):
            return shard_map(engine.gather_flat, mesh=mesh, in_specs=in_spec,
                             out_specs=P(None, None), check_vma=False)(x)

        ref = run(_engine(topo, "flat"))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(x),
                                      err_msg=f"{label} flat != input")
        for pol in POLICIES[1:]:
            got = run(_engine(topo, pol))
            assert np.array_equal(np.asarray(got), np.asarray(ref)), \
                f"{label}/{pol}: staged gather != flat gather"
        # explicit inner factor on the single-axis group
        if label == "single":
            for pol in POLICIES[1:]:
                got = run(_engine(topo, pol, inner=2))
                assert np.array_equal(np.asarray(got), np.asarray(ref)), \
                    f"{label}/{pol}/inner=2"


# ---------------------------------------------------------------------------
@check("vjp_matches_rs")
def _vjp_matches_rs():
    """Each policy's VJP == the explicit hop-1 reduce-scatter, compared
    inside one shard_map body so no ambient cotangent scaling interferes."""
    rng = np.random.default_rng(1)
    for label, topo, in_spec in _topos():
        mesh = topo.mesh
        x = jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)
        # ct varies per device so the reduction is non-trivial
        ct = jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)

        for pol in POLICIES:
            eng = _engine(topo, pol)

            def body(xs, cs):
                _, vjp = jax.vjp(eng.gather_flat, xs)
                (got,) = vjp(cs)
                want = C.hop1_reduce_scatter(cs, topo)  # flat reference
                want_policy = eng.hop1_reduce_scatter(cs)
                return got, want, want_policy

            got, want, want_policy = shard_map(
                body, mesh=mesh, in_specs=(in_spec, P(None, None)),
                out_specs=(in_spec, in_spec, in_spec), check_vma=False)(x, ct)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
                err_msg=f"{label}/{pol}: VJP != flat hop1_reduce_scatter")
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want_policy),
                err_msg=f"{label}/{pol}: VJP != staged hop1_reduce_scatter")


# ---------------------------------------------------------------------------
@check("int8_wire_gather")
def _int8_wire():
    from repro.core.quant import BLOCK

    topo = MiCSTopology(make_host_mesh(1, 1, 4, 1))
    mesh = topo.mesh
    n = 4 * BLOCK * 2
    x = jnp.asarray(np.random.default_rng(2).normal(size=(n,)) * 0.05,
                    jnp.float32)
    eng = _engine(topo, "inner_first", wire="int8")
    got = shard_map(eng.gather_flat, mesh=mesh, in_specs=P(("shard",)),
                    out_specs=P(None), check_vma=False)(x)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(x))
    blocks = np.asarray(x).reshape(-1, BLOCK)
    bound = np.abs(blocks).max(-1) / 254 + 1e-8
    # bf16 dequant output adds ~2^-8 relative rounding on top of int8 error
    assert np.all(err.reshape(-1, BLOCK) <= bound[:, None] * 1.05 + 2e-3), \
        err.max()

    # straight-through adjoint: grads flow and match the exact reduce-scatter
    ct = jnp.asarray(np.random.default_rng(3).normal(size=(n,)), jnp.float32)

    def body(xs, cs):
        _, vjp = jax.vjp(lambda v: eng.gather_flat(v).astype(jnp.float32), xs)
        (got,) = vjp(cs)
        want = C.hop1_reduce_scatter(cs, topo)
        return got, want

    got, want = shard_map(body, mesh=mesh, in_specs=(P(("shard",)), P(None)),
                          out_specs=(P(("shard",)), P(("shard",))),
                          check_vma=False)(x, ct)
    # the upstream cotangent passes through the bf16 compute-dtype cast
    # before the (fp32) reduce-scatter, so compare at bf16 resolution
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-2, atol=1e-2)


# ---------------------------------------------------------------------------
def _train_losses(mcfg, steps=3, seed=0):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh = make_host_mesh(1, 1, 4, 2)
    topo = MiCSTopology(mesh)
    model = build_model(cfg, tp=2)
    state = init_state(model, topo, seed=seed)
    step = build_train_step(
        model, topo, mcfg,
        OptConfig(total_steps=50, warmup_steps=0, lr_max=3e-3))
    rng = np.random.default_rng(7)
    s, b, t = 2, 8, 32
    batch = {
        "tokens": jnp.array(rng.integers(0, cfg.vocab, (s, b, t)), jnp.int32),
        "targets": jnp.array(rng.integers(0, cfg.vocab, (s, b, t)), jnp.int32),
        "mask": jnp.ones((s, b, t), jnp.float32),
    }
    losses = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


@check("prefetch_bitwise")
def _prefetch_bitwise():
    serial = _train_losses(MiCSConfig(micro_steps=2, prefetch=False))
    pre = _train_losses(MiCSConfig(micro_steps=2, prefetch=True))
    assert all(np.isfinite(serial)) and all(np.isfinite(pre))
    assert serial == pre, f"prefetch diverged: {serial} vs {pre}"
    # and with the paper-faithful 3-stage gather order
    serial3 = _train_losses(
        MiCSConfig(micro_steps=2, prefetch=False, gather_order="outer_first"))
    pre3 = _train_losses(
        MiCSConfig(micro_steps=2, prefetch=True, gather_order="outer_first"))
    assert serial3 == pre3, f"outer_first prefetch diverged: {serial3} vs {pre3}"


# ---------------------------------------------------------------------------
@check("prefetch_decode")
def _prefetch_decode():
    from repro.runtime.serving import build_serve_steps

    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh = make_host_mesh(1, 1, 2, 2)
    topo = MiCSTopology(mesh)
    model = build_model(cfg, tp=2)
    state = init_state(model, topo, seed=3)
    params = state["params"]
    rng = np.random.default_rng(11)
    toks = jnp.array(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)

    outs = {}
    for label, prefetch in (("serial", False), ("prefetch", True)):
        pre_fn, dec_fn = build_serve_steps(
            model, topo, MiCSConfig(prefetch=prefetch), cache_len=32)
        logits, caches = pre_fn(params, {"tokens": toks})
        tok = jnp.argmax(jnp.asarray(logits[:, -1:]), -1).astype(jnp.int32)
        lg2, tok2, _ = dec_fn(params, caches, tok, jnp.int32(16))
        outs[label] = (np.asarray(logits, np.float32),
                       np.asarray(lg2, np.float32), np.asarray(tok2))
    assert np.array_equal(outs["serial"][0], outs["prefetch"][0]), "prefill"
    assert np.array_equal(outs["serial"][1], outs["prefetch"][1]), "decode"
    assert np.array_equal(outs["serial"][2], outs["prefetch"][2]), "token"


# ---------------------------------------------------------------------------
@check("prefetch_census")
def _prefetch_census():
    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh = make_host_mesh(1, 1, 4, 2)
    topo = MiCSTopology(mesh)
    model = build_model(cfg, tp=2)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))

    counts = {}
    for label, prefetch in (("serial", False), ("prefetch", True)):
        step = build_train_step(
            model, topo, MiCSConfig(micro_steps=2, prefetch=prefetch),
            OptConfig(total_steps=10))
        lowered = step.lower(init_state_shapes(model),
                             make_batch_shapes(model, 8, 32, 2))
        stats = analyze(lowered.compile().as_text(), mesh_shape,
                        partition_axes=topo.partition_axes,
                        replication_axes=topo.replication_axes)
        counts[label] = stats["prefetch"]
        # stage attribution sees the staged hop-1 gathers
        stages = stats["by_stage"]
        assert any(k.startswith("param_gather") for k in stages), stages
    assert counts["serial"]["carried_all_gathers"] == 0, counts
    assert counts["prefetch"]["carried_all_gathers"] > 0, counts
    RESULTS["prefetch_census_detail"] = counts


print(json.dumps(RESULTS, indent=1, default=str))
