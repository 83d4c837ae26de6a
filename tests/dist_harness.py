"""Distributed-correctness harness, run in a subprocess with 8 virtual CPU
devices (keeps the main pytest process at 1 device, per the dry-run rules).

Prints one JSON object with named check results; tests/test_distributed.py
asserts on them.  Checks:

  hier_gather        hierarchical all-gather (both stage orders, single- and
                     multi-axis partition groups) == flat all-gather, values
                     and gradients
  mics_fidelity      MiCS (p=2, repl/pod=2, tp=2) training == single-device
                     training (paper Fig 16 analogue)
  zero3_equiv        ZeRO-3 configuration (partition = all data axes) matches
  alt_sync_equiv     alternative schedule (Fig 14) is numerically identical
  hier_train_equiv   hierarchical gather on == off, same losses
  compress_hop2      bf16-compressed hop 2 stays close
  decode_consistency prefill+decode logits == teacher-forced forward
  init_state_placement init_state on repl=2 x shard=2 == on one device,
                     placed in the state's shardings, moments zero
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
from repro.core.mics import (
    MiCSConfig, build_train_step, init_state, state_pspecs, state_shardings,
)
from repro.core.topology import MiCSTopology, make_host_mesh
from repro.models.build import build_model
from repro.optim.adamw import OptConfig

RESULTS = {}


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


# ---------------------------------------------------------------------------
@check("hier_gather")
def _hier_gather():
    mesh = make_host_mesh(2, 1, 4, 1)  # pod=2, shard=4
    x = jnp.arange(64.0).reshape(16, 4)

    def run(fn, in_spec):
        return shard_map(fn, mesh=mesh, in_specs=in_spec,
                         out_specs=P(None, None), check_vma=False)(x)

    # single-axis partition group (p=4), both orders, values
    topo = MiCSTopology(mesh, partition_axes=("shard",),
                        replication_axes=("pod", "repl"))
    ref = run(lambda xs: C.flat_all_gather(xs, ("shard",)), P("shard", None))
    for order in ("inner_first", "outer_first"):
        got = run(
            lambda xs: C.hierarchical_all_gather(xs, topo, order=order, inner=2),
            P("shard", None))
        np.testing.assert_allclose(got, ref, err_msg=order)

    # gradients flow identically through flat and both staged orders
    w = jnp.arange(64.0).reshape(16, 4) / 64.0

    def make_loss(gather):
        def f(xv):
            def body(xs):
                full = gather(xs)
                return jnp.sum(full ** 2) / mesh.size
            return jnp.sum(
                shard_map(body, mesh=mesh, in_specs=P("shard", None),
                          out_specs=P(), check_vma=False)(xv))
        return f

    gref = jax.grad(make_loss(lambda xs: C.flat_all_gather(xs, ("shard",))))(w)
    for order in ("inner_first", "outer_first"):
        g = jax.grad(make_loss(
            lambda xs: C.hierarchical_all_gather(xs, topo, order=order, inner=2)
        ))(w)
        np.testing.assert_allclose(g, gref, rtol=1e-6, err_msg=f"grad {order}")

    # multi-axis partition group (pod x shard), both orders
    topo2 = MiCSTopology(mesh, partition_axes=("pod", "shard"),
                         replication_axes=("repl",))
    ref2 = run(lambda xs: C.flat_all_gather(xs, ("pod", "shard")),
               P(("pod", "shard"), None))
    for order in ("inner_first", "outer_first"):
        got = run(lambda xs: C.hierarchical_all_gather(xs, topo2, order=order),
                  P(("pod", "shard"), None))
        np.testing.assert_allclose(got, ref2, err_msg=f"multiaxis {order}")


# ---------------------------------------------------------------------------
def _train_losses(mesh_dims, mcfg, partition_axes=("shard",), steps=4, seed=0,
                  arch="llama3.2-1b"):
    cfg = smoke_variant(get_config(arch))
    mesh = make_host_mesh(*mesh_dims)
    repl_axes = tuple(a for a in ("pod", "repl") if a not in partition_axes)
    topo = MiCSTopology(mesh, partition_axes=partition_axes,
                        replication_axes=repl_axes)
    tp = mesh_dims[3]
    model = build_model(cfg, tp=tp)
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
    return np.array(losses)


REF = {}


@check("mics_fidelity")
def _fidelity():
    """Paper Fig 16 analogue.  Note: tp=2 and tp=1 initialize TP-local
    shards from different RNG streams, so this is a *convergence-curve*
    comparison (like the paper's DeepSpeed-vs-MiCS loss overlay), not a
    bitwise one — the bitwise checks are the fixed-tp partition/schedule
    equivalences below."""
    REF["single"] = _train_losses((1, 1, 1, 1), MiCSConfig(micro_steps=2))
    REF["mics"] = _train_losses((2, 1, 2, 2), MiCSConfig(micro_steps=2))
    assert np.all(np.isfinite(REF["mics"]))
    np.testing.assert_allclose(REF["mics"], REF["single"], rtol=0.02, atol=0.03)


@check("zero3_equiv")
def _zero3():
    z3 = _train_losses((2, 1, 2, 2), MiCSConfig(micro_steps=2),
                       partition_axes=("pod", "shard"))
    np.testing.assert_allclose(z3, REF["mics"], rtol=0.02, atol=0.03)


@check("alt_sync_equiv")
def _alt():
    alt = _train_losses((2, 1, 2, 2),
                        MiCSConfig(micro_steps=2, sync_mode="allreduce_slice"))
    np.testing.assert_allclose(alt, REF["mics"], rtol=2e-3, atol=2e-3)


@check("hier_train_equiv")
def _hier_train():
    flat = _train_losses((1, 1, 4, 2),
                         MiCSConfig(micro_steps=2, hierarchical=False))
    hier = _train_losses((1, 1, 4, 2),
                         MiCSConfig(micro_steps=2, hierarchical=True,
                                    gather_order="outer_first"))
    # first step is bit-identical; later steps drift only via bf16
    # reduction order in the staged backward reduce-scatter
    np.testing.assert_allclose(hier[0], flat[0], rtol=1e-6)
    np.testing.assert_allclose(hier, flat, rtol=2e-3, atol=5e-3)


@check("compress_hop2")
def _compress():
    comp = _train_losses((2, 1, 2, 2),
                         MiCSConfig(micro_steps=2, compress_hop2=True))
    np.testing.assert_allclose(comp, REF["mics"], rtol=0.05, atol=0.05)


@check("moe_tp_equiv")
def _moe_tp():
    """Token-sharded expert-parallel MoE (tp=4) == single-device model."""
    one = _train_losses((1, 1, 1, 1), MiCSConfig(micro_steps=2),
                        arch="deepseek-moe-16b", seed=2)
    ep = _train_losses((1, 1, 2, 4), MiCSConfig(micro_steps=2),
                       arch="deepseek-moe-16b", seed=2)
    np.testing.assert_allclose(ep, one, rtol=0.03, atol=0.05)


@check("mla_tp_equiv")
def _mla_tp():
    """MLA heads and held experts split over the model axis (tp=2) ==
    single-device model (deepseek-v2-lite: a dense layer, then expert
    layers holding 4 of 8 experts)."""
    one = _train_losses((1, 1, 1, 1), MiCSConfig(micro_steps=2),
                        arch="deepseek-v2-lite", seed=5)
    tp2 = _train_losses((1, 1, 2, 2), MiCSConfig(micro_steps=2),
                        arch="deepseek-v2-lite", seed=5)
    np.testing.assert_allclose(tp2, one, rtol=0.03, atol=0.05)


@check("griffin_partition_equiv")
def _griffin_partition():
    """Griffin (RG-LRU + MQA kv-group gathers) under MiCS partitioning:
    p=2 vs p=1 at the same tp=2 (identical logical init — TP-local RNG
    streams depend only on (stack, tp)) must train identically."""
    p2 = _train_losses((1, 1, 2, 2), MiCSConfig(micro_steps=2),
                       arch="recurrentgemma-2b", seed=3)
    p1 = _train_losses((1, 2, 1, 2), MiCSConfig(micro_steps=2),
                       arch="recurrentgemma-2b", seed=3)
    np.testing.assert_allclose(p2, p1, rtol=2e-3, atol=5e-3)


@check("mlstm_chunk_train_equiv")
def _mlstm_chunk():
    """Chunkwise mLSTM training == sequential-scan training (xlstm)."""
    seq = _train_losses((1, 1, 2, 1), MiCSConfig(micro_steps=2),
                        arch="xlstm-125m", seed=4)
    chk = _train_losses((1, 1, 2, 1),
                        MiCSConfig(micro_steps=2, mlstm_chunk=8),
                        arch="xlstm-125m", seed=4)
    np.testing.assert_allclose(chk, seq, rtol=5e-3, atol=1e-2)


# ---------------------------------------------------------------------------
@check("init_state_placement")
def _init_placement():
    """The state is drawn on one device and placed a pool at a time: on a
    repl=2 x shard=2 mesh it holds the one-device state's values, in the
    state's shardings, with zero moments."""
    cfg = smoke_variant(get_config("deepseek-v2-lite"))
    model = build_model(cfg, tp=1)
    one = init_state(model, MiCSTopology(make_host_mesh(1, 1, 1, 1)), seed=4)
    topo = MiCSTopology(make_host_mesh(1, 2, 2, 1))
    got = init_state(model, topo, seed=4)
    want_sh = state_shardings(model, topo)
    assert jax.tree.structure(got) == jax.tree.structure(one)
    for leaf, ref, sh in zip(jax.tree.leaves(got), jax.tree.leaves(one),
                             jax.tree.leaves(want_sh)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), leaf.sharding
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))
    for name in ("m", "v"):
        assert all(not np.asarray(a).any()
                   for a in jax.tree.leaves(got[name])), name


# ---------------------------------------------------------------------------
@check("decode_consistency")
def _decode():
    from repro.core.comm import CommEngine
    from repro.core.topology import MODEL_AXIS
    from repro.models import layers as L
    from repro.models import lm as lmmod
    from repro.runtime.serving import build_serve_steps

    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh = make_host_mesh(1, 1, 2, 2)
    topo = MiCSTopology(mesh)
    model = build_model(cfg, tp=2)
    state = init_state(model, topo, seed=3)
    params = state["params"]

    cache_len = 32
    prefill_fn, decode_fn = build_serve_steps(
        model, topo, MiCSConfig(), cache_len)

    rng = np.random.default_rng(11)
    b, t0 = 2, 16
    toks = jnp.array(rng.integers(0, cfg.vocab, (b, t0 + 4)), jnp.int32)
    logits0, caches = prefill_fn(params, {"tokens": toks[:, :t0]})

    comm = CommEngine.from_config(topo, MiCSConfig())
    ctx = L.Ctx(mode="train", tp=2, tp_axis=MODEL_AXIS)

    def fwd(p, tokens):
        hidden, _, _, t_head = lmmod.forward(
            model, p, comm, ctx, {"tokens": tokens})
        return lmmod.lm_logits(model, t_head, hidden, ctx)

    sm = shard_map(
        fwd, mesh=mesh,
        in_specs=(state_pspecs(model, topo)["params"],
                  P(topo.data_axes, None)),
        out_specs=P(topo.data_axes, None, MODEL_AXIS), check_vma=False)
    ref_logits = np.asarray(jax.jit(sm)(params, toks))

    errs = []
    for i in range(4):
        pos = jnp.int32(t0 + i)
        logits, next_tok, caches = decode_fn(
            params, caches, toks[:, t0 + i: t0 + i + 1], pos)
        got = np.asarray(logits)[:, 0]
        want = ref_logits[:, t0 + i]
        errs.append(float(np.max(np.abs(got - want))))
    errs.append(float(np.max(np.abs(
        np.asarray(logits0)[:, 0] - ref_logits[:, t0 - 1]))))
    assert max(errs) < 0.15, f"decode logits deviate: {errs}"


print(json.dumps(RESULTS, indent=1, default=str))
