"""Boundary-scheduler correctness harness, run in a subprocess with 8
virtual CPU devices (same pattern as comm_harness.py).  Prints one JSON
object with named check results; tests/test_schedule.py asserts on them.
Checks:

  bucket_plan          partition_buckets/plan_boundary cover every element
                       exactly once, respect the byte cap, and degenerate
                       correctly (one bucket, bucket > total bytes)
  bitwise_bucket_sizes bucketed boundary == serial boundary, bitwise on
                       params/m/v and metrics, across bucket sizes
                       including the one-bucket and bucket>total-bytes
                       degenerate cases (hop 2 live: repl=2)
  bitwise_topologies   the same equivalence under all three gather
                       topologies and the bf16/int8 wire dtypes (the
                       boundary must be schedule-invariant whatever the
                       hop-1 policy feeding it)
  bitwise_compress     ... and under bf16-compressed hop-2 wire
  census_interleave    the compiled bucketed step's HLO shows hop-2 at
                       bucket granularity (hop2_ops == plan buckets,
                       max payload <= bucket bytes) interleaved with
                       norm/optimizer compute; the serial reference keeps
                       pool-granular hop-2 ops
  approx_clip_inactive clip_mode='approx' with the clip never engaging:
                       loss/grad_norm trajectories bitwise identical to
                       exact across bucket sizes (incl. the one-bucket-
                       per-pool degenerate case); params agree to the
                       final ulp (identical update arithmetic, different
                       XLA fusion of the elementwise AdamW chain)
  approx_zero_grad     all-zero grads (zero loss mask): gnorm 0, guarded
                       clip division — approx == exact bitwise, finite
  approx_clip_active_bounded
                       clip engaged: approx's one-bucket-stale factor may
                       drift, bounded by APPROX_CLIP_LOSS_RTOL on the
                       final loss of a short convergence run (loss must
                       also actually decrease under both clips)
  approx_int8_hop2     approx clip composes with the int8-compressed
                       hop-2 wire (finite metrics over 2 steps)
  approx_census_interleave
                       the compiled approx step still shows bucket-granular
                       hop-2, and strictly MORE compute between hop-2 ops
                       than the exact bucketed step — the AdamW updates
                       pipelined into the gaps
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

from repro.configs import get_config, smoke_variant
from repro.core.flat_param import partition_buckets
from repro.core.mics import (
    MiCSConfig, build_train_step, init_state, init_state_shapes,
    make_batch_shapes,
)
from repro.core.schedule import (
    APPROX_CLIP_LOSS_RTOL, GRAD_ITEMSIZE, plan_boundary,
)
from repro.core.topology import MiCSTopology, make_host_mesh
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.roofline.hlo_stats import analyze

RESULTS = {}

STEPS = 2
MICRO = 2
TINY_MB = 0.02          # forces several buckets per pool on the smoke model
HUGE_MB = 1e6           # bucket > total bytes: one bucket per pool


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


def _setup():
    """repl=2 so hop 2 is a live collective; p=2, tp=2."""
    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh = make_host_mesh(1, 2, 2, 2)
    topo = MiCSTopology(mesh)
    model = build_model(cfg, tp=2)
    rng = np.random.default_rng(7)
    b, t = 8, 32
    batch = {
        "tokens": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                            jnp.int32),
        "targets": jnp.array(rng.integers(0, cfg.vocab, (MICRO, b, t)),
                             jnp.int32),
        "mask": jnp.ones((MICRO, b, t), jnp.float32),
    }
    return cfg, topo, model, batch


CFG, TOPO, MODEL, BATCH = _setup()


def _run(mcfg, steps=STEPS, seed=1, oc=None, batch=None):
    batch = BATCH if batch is None else batch
    state = init_state(MODEL, TOPO, seed=seed)
    step = build_train_step(MODEL, TOPO, mcfg,
                            oc or OptConfig(total_steps=50, warmup_steps=0,
                                            lr_max=3e-3))
    metrics = []
    for _ in range(steps):
        state, m = step(state, batch)
        metrics.append((float(m["loss"]), float(m["grad_norm"])))
    return metrics, jax.tree.map(np.asarray, state)


def _assert_state_equal(a_state, b_state, tag):
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(a_state)[0],
            jax.tree_util.tree_flatten_with_path(b_state)[0]):
        assert np.array_equal(a, b), f"{tag}: state leaf {path} diverged"


def _assert_bitwise(mcfg_kw, tag):
    serial, s_state = _run(MiCSConfig(boundary_schedule="serial", **mcfg_kw))
    bucketed, b_state = _run(
        MiCSConfig(boundary_schedule="bucketed", **mcfg_kw))
    assert all(np.isfinite(v) for row in serial for v in row), serial
    assert serial == bucketed, \
        f"{tag}: metrics diverged {serial} vs {bucketed}"
    _assert_state_equal(s_state, b_state, tag)


# ---------------------------------------------------------------------------
@check("bucket_plan")
def _bucket_plan():
    # helper-level: exact cover, byte cap, degenerate cases
    per = partition_buckets(10_000, 0.01, 4)      # 2500-elem buckets
    assert per[0] == (0, 2500) and per[-1][1] == 10_000
    assert all(hi - lo <= 2500 for lo, hi in per)
    covered = [e for lo, hi in per for e in range(lo, hi)]
    assert covered == list(range(10_000))
    assert partition_buckets(100, 1e6, 4) == ((0, 100),)   # bucket > total
    assert partition_buckets(0, 1.0, 4) == ()
    # plan-level: canonical order, counts, shard sizing
    tiny = plan_boundary(MODEL, TOPO, mode="bucketed", bucket_mb=TINY_MB)
    huge = plan_boundary(MODEL, TOPO, mode="bucketed", bucket_mb=HUGE_MB)
    n_pools = len(MODEL.all_pools())
    assert huge.n_buckets == n_pools, huge.describe()
    assert tiny.n_buckets > n_pools, tiny.describe()
    cap = int(TINY_MB * 1e6)
    assert all(b.elems * GRAD_ITEMSIZE <= cap for b in tiny.buckets)
    p = TOPO.partition_size
    for pool in MODEL.all_pools():
        stack, _, flat_len = MODEL.global_flat_shapes()[pool.name]
        pool_bkts = tiny.pool_buckets(pool.name)
        assert pool_bkts[0].lo == 0
        assert pool_bkts[-1].hi == stack * flat_len // p
    RESULTS["bucket_plan_detail"] = {
        "tiny": tiny.describe(), "huge": huge.describe()}


# ---------------------------------------------------------------------------
@check("bitwise_bucket_sizes")
def _bitwise_bucket_sizes():
    for mb in (TINY_MB, 0.2, HUGE_MB):   # several / few / one bucket per pool
        _assert_bitwise(dict(micro_steps=MICRO, hop2_bucket_mb=mb),
                        tag=f"bucket_mb={mb}")


# ---------------------------------------------------------------------------
@check("bitwise_topologies")
def _bitwise_topologies():
    combos = [
        dict(hierarchical=False),                              # flat
        dict(gather_order="outer_first"),                      # 3-stage
        dict(quant_gather=True),                               # int8 wire
        dict(gather_dtype=jnp.float32),                        # fp32 wire
    ]
    for kw in combos:
        _assert_bitwise(
            dict(micro_steps=MICRO, hop2_bucket_mb=TINY_MB, **kw),
            tag=f"combo={kw}")


# ---------------------------------------------------------------------------
@check("bitwise_compress")
def _bitwise_compress():
    _assert_bitwise(
        dict(micro_steps=MICRO, hop2_bucket_mb=TINY_MB, compress_hop2=True),
        tag="compress_hop2")


# ---------------------------------------------------------------------------
@check("census_interleave")
def _census_interleave():
    mesh_shape = dict(zip(TOPO.mesh.axis_names, TOPO.mesh.devices.shape))
    plans = {
        "serial": plan_boundary(MODEL, TOPO, mode="serial",
                                bucket_mb=TINY_MB),
        "bucketed": plan_boundary(MODEL, TOPO, mode="bucketed",
                                  bucket_mb=TINY_MB),
    }
    census = {}
    for label in ("serial", "bucketed"):
        step = build_train_step(
            MODEL, TOPO,
            MiCSConfig(micro_steps=MICRO, boundary_schedule=label,
                       hop2_bucket_mb=TINY_MB),
            OptConfig(total_steps=10))
        stats = analyze(
            step.lower(init_state_shapes(MODEL),
                       make_batch_shapes(MODEL, MICRO * 8, 32, MICRO))
                .compile().as_text(),
            mesh_shape,
            partition_axes=TOPO.partition_axes,
            replication_axes=TOPO.replication_axes)
        census[label] = stats["boundary"]
        # hop-2 wire bytes are schedule-invariant (same reduction, resliced)
        census[label]["hop2_wire_bytes"] = \
            stats["by_stage"]["hop2"]["wire_bytes"]

    n_pools = len(MODEL.all_pools())
    ser, bkt = census["serial"], census["bucketed"]
    assert ser["hop2_ops"] == n_pools, census
    assert bkt["hop2_ops"] == plans["bucketed"].n_buckets > n_pools, census
    assert bkt["hop2_max_operand_bytes"] <= int(TINY_MB * 1e6), census
    # the pipeline's signature: compute issued between hop-2 collectives
    assert bkt["interleaved"] and bkt["compute_between_hop2"] > 0, census
    assert bkt["hop2_wire_bytes"] == ser["hop2_wire_bytes"], census
    RESULTS["census_interleave_detail"] = census


# ---------------------------------------------------------------------------
def _bucketed(clip="exact", mb=TINY_MB, **kw):
    return MiCSConfig(boundary_schedule="bucketed", micro_steps=MICRO,
                      hop2_bucket_mb=mb, clip_mode=clip, **kw)


@check("approx_clip_inactive")
def _approx_clip_inactive():
    # with the clip never engaging, the stale factor and the exact factor
    # are the same 1.0 — metrics must be bitwise identical at any bucket
    # count (incl. one bucket per pool: HUGE_MB) and params must agree to
    # the final ulp (same arithmetic, different XLA fusion)
    oc = OptConfig(total_steps=50, warmup_steps=0, lr_max=3e-3,
                   clip_norm=1e9)
    for mb in (TINY_MB, 0.2, HUGE_MB):
        exact, e_state = _run(_bucketed(mb=mb), oc=oc)
        approx, a_state = _run(_bucketed("approx", mb=mb), oc=oc)
        assert all(np.isfinite(v) for row in exact for v in row), exact
        assert exact == approx, f"mb={mb}: {exact} vs {approx}"
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(e_state)[0],
                jax.tree_util.tree_flatten_with_path(a_state)[0]):
            assert np.allclose(a, b, rtol=0, atol=1e-6), \
                f"mb={mb}: state leaf {path} off by " \
                f"{np.max(np.abs(np.float64(a) - np.float64(b)))}"


@check("approx_zero_grad")
def _approx_zero_grad():
    # all-zero loss mask: zero grads in every bucket, gnorm 0, and the
    # clip factor hits its guarded 0-norm branch in both modes
    zb = dict(BATCH, mask=jnp.zeros_like(BATCH["mask"]))
    exact, e_state = _run(_bucketed(), batch=zb)
    approx, a_state = _run(_bucketed("approx"), batch=zb)
    assert all(np.isfinite(v) for row in approx for v in row), approx
    assert all(g == 0.0 for _, g in exact), exact
    assert exact == approx, f"{exact} vs {approx}"
    _assert_state_equal(e_state, a_state, "zero_grad")


@check("approx_clip_active_bounded")
def _approx_clip_active():
    # convergence smoke with the clip engaged (smoke-model gnorm ~15 >>
    # clip_norm=1.0): the stale factor drifts the trajectory, but the
    # final loss stays within APPROX_CLIP_LOSS_RTOL of exact and both
    # runs actually learn
    steps = 12
    oc = OptConfig(total_steps=steps, warmup_steps=0, lr_max=3e-3,
                   clip_norm=1.0)
    exact, _ = _run(_bucketed(), steps=steps, oc=oc)
    approx, _ = _run(_bucketed("approx"), steps=steps, oc=oc)
    assert all(np.isfinite(v) for row in approx for v in row), approx
    # identical params at step 0 => identical first loss, drift after
    assert approx[0][0] == exact[0][0], (approx[0], exact[0])
    assert exact[-1][0] < exact[0][0], exact
    assert approx[-1][0] < approx[0][0], approx
    rtol = abs(approx[-1][0] - exact[-1][0]) / abs(exact[-1][0])
    assert rtol <= APPROX_CLIP_LOSS_RTOL, (rtol, exact[-1], approx[-1])
    RESULTS["approx_convergence_detail"] = {
        "steps": steps, "final_exact": exact[-1][0],
        "final_approx": approx[-1][0], "rtol": rtol}


@check("approx_int8_hop2")
def _approx_int8_hop2():
    # the approx pipeline folds per-bucket psums from the *dequantized*
    # int8 hop-2 wire — composition must stay finite
    approx, _ = _run(_bucketed("approx", compress_hop2="int8"))
    assert all(np.isfinite(v) for row in approx for v in row), approx


@check("approx_census_interleave")
def _approx_census_interleave():
    mesh_shape = dict(zip(TOPO.mesh.axis_names, TOPO.mesh.devices.shape))
    plan = plan_boundary(MODEL, TOPO, mode="bucketed", bucket_mb=TINY_MB)
    census = {}
    for clip in ("exact", "approx"):
        step = build_train_step(MODEL, TOPO, _bucketed(clip),
                                OptConfig(total_steps=10))
        stats = analyze(
            step.lower(init_state_shapes(MODEL),
                       make_batch_shapes(MODEL, MICRO * 8, 32, MICRO))
                .compile().as_text(),
            mesh_shape,
            partition_axes=TOPO.partition_axes,
            replication_axes=TOPO.replication_axes)
        census[clip] = stats["boundary"]
    for clip in ("exact", "approx"):
        assert census[clip]["hop2_ops"] == plan.n_buckets, census
        assert census[clip]["interleaved"], census
    # the pipeline's signature: the AdamW updates land in the gaps
    # between hop-2 collectives, so the approx step has strictly more
    # compute there than the exact bucketed step (whose optimizer runs
    # after the last hop-2)
    assert census["approx"]["compute_between_hop2"] \
        > census["exact"]["compute_between_hop2"], census
    RESULTS["approx_census_detail"] = census


print(json.dumps(RESULTS, indent=1, default=str))
