"""qgZ correctness harness (int8 hop-1 / hop-2 gradient wires), run in a
subprocess with 8 virtual CPU devices (same pattern as comm_harness.py).
Prints one JSON object with named check results; tests/test_qgz.py asserts
on them.  Checks:

  quant_rs_routing       quantized_reduce_scatter routes/reorders chunks
                         exactly like psum_scatter (single- and multi-axis
                         partition groups, all three topologies): with one
                         contributor and grid-exact data the quantizer is
                         lossless, so any mismatch is a routing bug
  quant_rs_accuracy      dense multi-contributor reduce-scatter stays
                         within the blockwise quantization error bound
  step_seed_dither       the threaded step seed (ISSUE 5 satellite) is
                         bitwise reproducible per seed, draws distinct
                         dither across seeds on the same payload, and
                         stays within the error bound
  hop1_bf16_bitwise      hop1_wire_dtype='bf16' under the bf16 gather wire
                         is bitwise the default path (the cast is identity)
  int8_hop1_convergence  tiny-LM training with the int8 qgZ hop-1 tracks
                         the fp32 reference (finite, decreasing, final
                         loss within tolerance), for the bf16 gather wire
                         and for the full int8 qwZ+qgZ combination
  int8_hop2_boundary     compress_hop2='int8' trains under both boundary
                         schedules; serial and bucketed agree to
                         quantization error (not bitwise — blocks follow
                         the payload), and the compiled bucketed step's
                         census shows one int8 hop-2 leg per bucket
                         interleaved with boundary compute
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import json
import traceback

import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, smoke_variant
from repro.core import collectives as C
from repro.core.mics import (
    MiCSConfig, build_train_step, init_state, init_state_shapes,
    make_batch_shapes,
)
from repro.core.quant import BLOCK
from repro.core.schedule import plan_boundary
from repro.core.topology import MiCSTopology, make_host_mesh
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.roofline.hlo_stats import analyze

RESULTS = {}
STEPS = 6
MICRO = 2


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


def _grid_exact_data(n):
    """Integers with per-block absmax pinned to 127 -> scale == 1 exactly,
    so quantization (nearest or stochastic) is lossless."""
    rng = np.random.default_rng(3)
    ints = jnp.asarray(rng.integers(-127, 128, size=(n,)), jnp.float32)
    return ints.at[::BLOCK].set(127.0)


# ---------------------------------------------------------------------------
@check("quant_rs_routing")
def _quant_rs_routing():
    single = MiCSTopology(make_host_mesh(1, 2, 4, 1),
                          partition_axes=("shard",),
                          replication_axes=("pod", "repl"))
    multi = MiCSTopology(make_host_mesh(2, 1, 4, 1),
                         partition_axes=("pod", "shard"),
                         replication_axes=("repl",))
    data = _grid_exact_data(4 * 4096)
    for label, topo in (("single", single), ("multi", multi)):
        axes = topo.partition_axes

        def coord():
            idx = 0
            for a in axes:
                idx = idx * topo.axis_size(a) + lax.axis_index(a)
            return idx

        for topology in ("flat", "inner_first", "outer_first"):
            def body(g):
                g = jnp.where(coord() == 0, g, 0.0)  # single contributor
                got = C.quantized_reduce_scatter(g, topo, topology=topology)
                want = lax.psum_scatter(g, axes, scatter_dimension=0,
                                        tiled=True)
                return got, want

            got, want = shard_map(
                body, mesh=topo.mesh, in_specs=P(None),
                out_specs=(P(axes), P(axes)), check_vma=False)(data)
            assert np.array_equal(np.asarray(got), np.asarray(want)), \
                f"{label}/{topology}: quantized RS misroutes chunks"


# ---------------------------------------------------------------------------
@check("quant_rs_accuracy")
def _quant_rs_accuracy():
    topo = MiCSTopology(make_host_mesh(1, 2, 4, 1),
                        partition_axes=("shard",),
                        replication_axes=("pod", "repl"))
    x = jnp.asarray(np.random.default_rng(5).normal(size=(4 * 4096,)),
                    jnp.float32)

    def body(g):
        g = g * (1.0 + 0.1 * lax.axis_index("shard").astype(jnp.float32))
        got = C.quantized_reduce_scatter(g, topo, topology="inner_first")
        want = lax.psum_scatter(g, ("shard",), scatter_dimension=0,
                                tiled=True)
        return got, want

    got, want = shard_map(body, mesh=topo.mesh, in_specs=P(None),
                          out_specs=(P(("shard",)), P(("shard",))),
                          check_vma=False)(x)
    err = np.abs(np.asarray(got) - np.asarray(want)).max()
    scale = np.abs(np.asarray(want)).max()
    assert err / scale < 0.05, (err, scale)
    RESULTS["quant_rs_accuracy_detail"] = {"rel_err": float(err / scale)}


# ---------------------------------------------------------------------------
@check("step_seed_dither")
def _step_seed_dither():
    """The threaded step seed replaces the payload-fingerprint dither
    component: distinct seeds draw distinct stochastic rounding on the SAME
    payload (value-independent decorrelation across steps), the same seed
    is bitwise reproducible, and every seed stays within the quantization
    error bound."""
    topo = MiCSTopology(make_host_mesh(1, 2, 4, 1),
                        partition_axes=("shard",),
                        replication_axes=("pod", "repl"))
    x = jnp.asarray(np.random.default_rng(11).normal(size=(4 * 4096,)),
                    jnp.float32)

    def body(g, seed):
        got = C.quantized_reduce_scatter(g, topo, topology="inner_first",
                                         seed=seed)
        want = lax.psum_scatter(g, ("shard",), scatter_dimension=0,
                                tiled=True)
        return got, want

    run = shard_map(body, mesh=topo.mesh, in_specs=(P(None), P()),
                    out_specs=(P(("shard",)), P(("shard",))),
                    check_vma=False)
    got0, want = run(x, jnp.int32(0))
    got0b, _ = run(x, jnp.int32(0))
    got1, _ = run(x, jnp.int32(1))
    assert np.array_equal(np.asarray(got0), np.asarray(got0b)), \
        "same step seed must be bitwise reproducible"
    assert not np.array_equal(np.asarray(got0), np.asarray(got1)), \
        "distinct step seeds must draw distinct dither"
    scale = np.abs(np.asarray(want)).max()
    for got in (got0, got1):
        err = np.abs(np.asarray(got) - np.asarray(want)).max()
        assert err / scale < 0.05, (err, scale)


# ---------------------------------------------------------------------------
def _train_losses(mcfg, steps=STEPS, repl=False):
    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh = make_host_mesh(1, 2, 2, 2) if repl else make_host_mesh(1, 1, 4, 2)
    topo = MiCSTopology(mesh)
    model = build_model(cfg, tp=2)
    state = init_state(model, topo, seed=9)
    step = build_train_step(
        model, topo, mcfg,
        OptConfig(total_steps=50, warmup_steps=0, lr_max=3e-3))
    rng = np.random.default_rng(7)
    b, t = 8, 32
    batch = {
        "tokens": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                            jnp.int32),
        "targets": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                             jnp.int32),
        "mask": jnp.ones((MICRO, b, t), jnp.float32),
    }
    losses = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


@check("hop1_bf16_bitwise")
def _hop1_bf16_bitwise():
    """Under the bf16 gather wire the cotangent is already bf16, so the
    explicit bf16 hop-1 cast is an identity — bitwise the default path."""
    ref = _train_losses(MiCSConfig(micro_steps=MICRO), steps=3)
    bf16 = _train_losses(
        MiCSConfig(micro_steps=MICRO, hop1_wire_dtype="bf16"), steps=3)
    assert ref == bf16, f"bf16 hop-1 diverged from default: {ref} vs {bf16}"


@check("int8_hop1_convergence")
def _int8_hop1_convergence():
    ref = _train_losses(MiCSConfig(micro_steps=MICRO))
    TOL = 0.05  # relative final-loss tolerance vs the fp32 reference
    combos = {
        "qgZ": MiCSConfig(micro_steps=MICRO, hop1_wire_dtype="int8"),
        "qwZ+qgZ": MiCSConfig(micro_steps=MICRO, hop1_wire_dtype="int8",
                              quant_gather=True),
    }
    detail = {"fp32": ref, "tolerance": TOL}
    for label, mcfg in combos.items():
        got = _train_losses(mcfg)
        detail[label] = got
        assert all(np.isfinite(got)), (label, got)
        assert got[-1] < got[0], (label, "loss did not decrease", got)
        rel = abs(got[-1] - ref[-1]) / abs(ref[-1])
        detail[f"{label}_rel_final"] = rel
        assert rel < TOL, (label, rel, got, ref)
    RESULTS["int8_hop1_convergence_detail"] = detail


# ---------------------------------------------------------------------------
@check("int8_hop2_boundary")
def _int8_hop2_boundary():
    """The int8 decompress leg of the boundary scheduler: both schedules
    train, agree to quantization error, and the bucketed census shows
    bucket-granular int8 hop-2 legs interleaved with compute."""
    BUCKET_MB = 0.02
    kw = dict(micro_steps=MICRO, compress_hop2="int8",
              hop2_bucket_mb=BUCKET_MB)
    serial = _train_losses(
        MiCSConfig(boundary_schedule="serial", **kw), steps=4, repl=True)
    bucketed = _train_losses(
        MiCSConfig(boundary_schedule="bucketed", **kw), steps=4, repl=True)
    assert all(np.isfinite(serial)) and all(np.isfinite(bucketed))
    assert serial[-1] < serial[0] and bucketed[-1] < bucketed[0]
    # quantization blocks follow the payload -> close, not bitwise
    rel = abs(serial[-1] - bucketed[-1]) / abs(serial[-1])
    assert rel < 0.05, (serial, bucketed)

    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh = make_host_mesh(1, 2, 2, 2)
    topo = MiCSTopology(mesh)
    model = build_model(cfg, tp=2)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    plan = plan_boundary(model, topo, mode="bucketed", bucket_mb=BUCKET_MB)
    step = build_train_step(
        model, topo, MiCSConfig(boundary_schedule="bucketed", **kw),
        OptConfig(total_steps=10))
    stats = analyze(
        step.lower(init_state_shapes(model),
                   make_batch_shapes(model, MICRO * 8, 32, MICRO))
            .compile().as_text(),
        mesh_shape,
        partition_axes=topo.partition_axes,
        replication_axes=topo.replication_axes)
    census = stats["boundary"]
    assert census["hop2_ops"] == plan.n_buckets, (census, plan.describe())
    assert census["interleaved"], census
    # the int8 q payload is ~1/4 the fp32 bucket bytes
    assert census["hop2_max_operand_bytes"] <= int(BUCKET_MB * 1e6) / 4 * 1.1
    RESULTS["int8_hop2_boundary_detail"] = {
        "serial": serial, "bucketed": bucketed, "rel_final": rel,
        "census": census, "n_buckets": plan.n_buckets,
    }


print(json.dumps(RESULTS, indent=1, default=str))
