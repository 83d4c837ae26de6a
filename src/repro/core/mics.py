"""The MiCS engine: scale-aware partitioned training step with 2-hop
gradient synchronization (paper §3), plus the ZeRO-3 and alternative-schedule
baselines used in the ablations.

Schedule (one jitted step = one gradient-accumulation boundary, s micro-steps):

  for each micro-step (lax.scan):
      per layer (lax.scan inside the model):
          all-gather the layer's flat shard across the partition group
          (policy topology + wire dtype, §3.3) — issued one layer AHEAD of
          its compute under the default double-buffered prefetch schedule;
          compute under jax.checkpoint (ZeRO-3 semantics + activation
          checkpointing)
      backward: the gather's custom-VJP adjoint reduce-scatters gradients
          across the partition group -> hop 1 (§3.4), accumulated in fp32
  at the boundary (core/schedule.py, the boundary scheduler):
      psum over replication axes                 -> hop 2 (§3.4)
      global-norm clip, AdamW on fp32 shards (optimizer states partitioned)
      — run serially (reference) or as a bucketed software pipeline that
      issues bucket k's hop-2 while bucket k-1's norm/decompress compute
      runs, bitwise identical to the serial path
      (MiCSConfig(boundary_schedule=..., hop2_bucket_mb=...))

Every collective above is owned by ONE ``CommEngine`` (core/comm.py, see
DESIGN.md §4) built from (MiCSTopology, MiCSConfig).  ZeRO-3 baseline =
partition_axes spanning every data axis (hop 2 vanishes).  Alternative
schedule (Fig 14) = all-reduce full gradient each micro-step then slice —
selected by SyncPolicy, realized in the gather's custom_vjp.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import scopes
from repro.core.autotune import resolve_config
from repro.core.comm import CommEngine
from repro.core.schedule import (
    BOUNDARY_SCHEDULES, CLIP_MODES, apply_boundary, plan_boundary,
)
from repro.core.topology import MODEL_AXIS, MiCSTopology
from repro.models import layers as L
from repro.models import lm
from repro.models.lm import ModelDef
from repro.optim.adamw import OptConfig


@dataclasses.dataclass(frozen=True)
class MiCSConfig:
    """Knobs of the paper's three mechanisms + beyond-paper options.

    ``policy="auto"`` hands the communication knobs (``hierarchical``,
    ``gather_order``, ``hierarchy_inner``, the wire dtype, hop-2
    compression) to the bandwidth-aware autotuner (core/autotune.py), which
    ranks every candidate over the named ``link_profile``
    (core/linkmodel.py) and rewrites this config with the winner before the
    CommEngine is built.  Auto never changes numerics you did not opt into,
    per mechanism: the int8 gather wire needs ``quant_gather=True`` (its
    gradient adjoint stays exact), the compressed hop-2 wires need
    ``compress_hop2=True``/``"bf16"``/``"int8"``, and the lossy int8 qgZ
    hop-1 needs ``hop1_wire_dtype="int8"``; under ``policy="auto"`` those
    flags turn from orders into permissions.
    """

    micro_steps: int = 1
    hierarchical: bool = True
    gather_order: str = "inner_first"   # 'outer_first' = paper-faithful 3-stage
    gather_dtype: Any = jnp.bfloat16
    sync_mode: str = "2hop"             # '2hop' | 'allreduce_slice' (ablation)
    hierarchy_inner: int | None = None  # intra-"node" factor for staged gather
    compress_hop2: Any = False          # hop-2 wire: False/'fp32' | True/'bf16'
    #                                     | 'int8' (quantized all-reduce leg)
    scores_bf16: bool = False           # bf16 attention scores (§Perf)
    mlstm_chunk: int = 0                # chunkwise-parallel mLSTM (§Perf)
    quant_gather: bool = False          # int8 wire / serving-weight gathers
    hop1_wire_dtype: str = "fp32"       # 'fp32' | 'bf16' | 'int8' (ZeRO++ qgZ
    #                                     block-quantized hop-1 reduce-scatter)
    grad_rounding: str = "stochastic"   # int8 gradient quantizer rounding
    prefetch: bool = True               # double-buffered lookahead gathers
    policy: str = "manual"              # 'manual' | 'auto' (link-model tuner)
    link_profile: Any = "v5e"           # profile name or LinkProfile instance
    boundary_schedule: str = "bucketed"  # 'serial' (reference) | 'bucketed'
    hop2_bucket_mb: float = 32.0        # fixed-byte hop-2 pipeline bucket
    clip_mode: str = "exact"            # 'exact' global-norm barrier |
    #                                     'approx' one-bucket-stale pipeline
    carry_offload: str = "none"         # 'none' | 'host' prefetch-carry
    #                                     d2h/h2d stream (core/hostoffload.py)
    offload_opt: bool = False           # AdamW m/v shards live in host memory
    hbm_budget_gb: float | None = None  # per-device HBM budget (GiB) the
    #                                     memory planner gates policies on
    kv_dtype: str = "bf16"              # paged-KV block dtype: 'fp32' | 'bf16'
    #                                     | 'int8' (core/quant.py block scales;
    #                                     a permission under policy='auto')
    kv_block_size: int = 16             # tokens per paged-KV block
    max_resident_requests: int = 0      # serving residency cap per device;
    #                                     0 = derive from the memory planner

    def __post_init__(self):
        from repro.core.comm import (
            CARRY_OFFLOADS, GRAD_ROUNDINGS, HOP1_WIRE_DTYPES,
            HOP2_WIRE_DTYPES,
        )

        if self.policy not in ("manual", "auto"):
            raise ValueError(f"unknown policy {self.policy!r} "
                             "(expected 'manual' or 'auto')")
        if self.boundary_schedule not in BOUNDARY_SCHEDULES:
            raise ValueError(
                f"unknown boundary_schedule {self.boundary_schedule!r} "
                f"(expected one of {BOUNDARY_SCHEDULES})")
        if self.hop2_bucket_mb <= 0:
            raise ValueError(
                f"hop2_bucket_mb must be > 0, got {self.hop2_bucket_mb}")
        if self.clip_mode not in CLIP_MODES:
            raise ValueError(f"unknown clip_mode {self.clip_mode!r} "
                             f"(expected one of {CLIP_MODES})")
        if self.clip_mode == "approx" and self.boundary_schedule != "bucketed":
            raise ValueError(
                "clip_mode='approx' requires boundary_schedule='bucketed' "
                "(the approximate clip is a property of the bucket pipeline)")
        if self.carry_offload not in CARRY_OFFLOADS:
            raise ValueError(
                f"unknown carry_offload {self.carry_offload!r} "
                f"(expected one of {CARRY_OFFLOADS})")
        if self.carry_offload == "host" and not self.prefetch:
            raise ValueError(
                "carry_offload='host' requires prefetch=True (it offloads "
                "the prefetch schedule's carried buffer)")
        if self.hbm_budget_gb is not None and self.hbm_budget_gb <= 0:
            raise ValueError(
                f"hbm_budget_gb must be > 0, got {self.hbm_budget_gb}")
        if self.hop1_wire_dtype not in HOP1_WIRE_DTYPES:
            raise ValueError(
                f"unknown hop1_wire_dtype {self.hop1_wire_dtype!r} "
                f"(expected one of {HOP1_WIRE_DTYPES})")
        if self.grad_rounding not in GRAD_ROUNDINGS:
            raise ValueError(
                f"unknown grad_rounding {self.grad_rounding!r} "
                f"(expected one of {GRAD_ROUNDINGS})")
        if not (self.compress_hop2 in (False, True)
                or self.compress_hop2 in HOP2_WIRE_DTYPES):
            raise ValueError(
                f"compress_hop2 must be a bool or one of {HOP2_WIRE_DTYPES}, "
                f"got {self.compress_hop2!r}")
        if self.kv_dtype not in ("fp32", "bf16", "int8"):
            raise ValueError(
                f"unknown kv_dtype {self.kv_dtype!r} "
                "(expected 'fp32', 'bf16' or 'int8')")
        if self.kv_block_size < 1:
            raise ValueError(
                f"kv_block_size must be >= 1, got {self.kv_block_size}")
        if self.max_resident_requests < 0:
            raise ValueError(
                "max_resident_requests must be >= 0 (0 = planner-derived), "
                f"got {self.max_resident_requests}")


# ---------------------------------------------------------------------------
# state containers + shardings
# ---------------------------------------------------------------------------

def init_state_shapes(model: ModelDef, *,
                      offload_opt: bool = False) -> dict[str, Any]:
    """Global ShapeDtypeStructs for params/m/v/step (no allocation).

    With ``offload_opt=True`` the AdamW moments live in the host stash
    (core/hostoffload.py), not the device state: ``m``/``v`` are absent.
    """
    shapes = model.global_flat_shapes()
    flat = {
        name: jax.ShapeDtypeStruct(shape, jnp.float32)
        for name, shape in shapes.items()
    }
    out = {
        "params": flat,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if not offload_opt:
        out["m"], out["v"] = dict(flat), dict(flat)
    return out


def state_pspecs(model: ModelDef, topo: MiCSTopology, *,
                 offload_opt: bool = False) -> dict[str, Any]:
    pool_spec = P(None, MODEL_AXIS, topo.partition_axes)
    flat = {name: pool_spec for name in model.global_flat_shapes()}
    out = {"params": flat, "step": P()}
    if not offload_opt:
        out["m"], out["v"] = dict(flat), dict(flat)
    return out


def state_shardings(model: ModelDef, topo: MiCSTopology, *,
                    offload_opt: bool = False):
    return jax.tree.map(
        lambda spec: NamedSharding(topo.mesh, spec),
        state_pspecs(model, topo, offload_opt=offload_opt),
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_pspecs(model: ModelDef, topo: MiCSTopology, *, micro: bool = True):
    """PartitionSpecs for a training batch dict."""
    lead = (None,) if micro else ()
    base = {
        "tokens": P(*lead, topo.data_axes, None),
        "targets": P(*lead, topo.data_axes, None),
        "mask": P(*lead, topo.data_axes, None),
    }
    if model.cfg.family == "vlm":
        base["vision"] = P(*lead, topo.data_axes, None, None)
    if model.cfg.family == "encdec":
        base["audio"] = P(*lead, topo.data_axes, None, None)
    return base


def init_state(model: ModelDef, topo: MiCSTopology, seed: int = 0, *,
               offload_opt: bool = False):
    """Materialize sharded fp32 state (for runnable-scale models).

    The init is computed on a single device and distributed with
    ``device_put``.  Jitting it with sharded+replicated ``out_shardings``
    is NOT equivalent: XLA's SPMD partitioner may establish the replicated
    axes by all-reducing identical per-replica contributions, which *sums*
    them — observed doubling every parameter on CPU meshes with a
    replication axis (pod/repl > 1).  device_put is exact and makes the
    initial state a pure function of (model, seed), independent of topology.

    ``device_put`` cuts one slice per target device on the source device
    before it copies them.  The pools are placed one at a time, each copy
    finished and its one-device source dropped before the next, so the
    first device never holds more than one pool's slices.  The moments
    are zeros and are made in place: zeros are exact under any partitioning.
    """
    shapes = model.global_flat_shapes()
    shardings = state_shardings(model, topo, offload_opt=offload_opt)

    def _init(key):
        import zlib

        flat = {}
        for pool in model.all_pools():
            stack, tp, _ = shapes[pool.name]
            pool_key = jax.random.fold_in(
                key, zlib.crc32(pool.name.encode()) % (2**31))
            # One flat row per (layer, tp rank), each kept a 1-D buffer of
            # its own: drawn straight into the [stack, tp, n] layout, the
            # random fusion takes the TPU compiler half a minute per pool.
            rows = [lax.optimization_barrier(pool.layout.init_flat(k))
                    for k in jax.random.split(pool_key, stack * tp)]
            flat[pool.name] = jnp.stack(rows).reshape(stack, tp, -1)
        return flat

    drawn = jax.jit(_init)(jax.random.key(seed))
    params = {name: jax.block_until_ready(
        jax.device_put(drawn.pop(name), shardings["params"][name]))
        for name in list(drawn)}
    out = {"params": params,
           "step": jax.device_put(jnp.int32(0), shardings["step"])}
    if not offload_opt:
        # Offloaded moments zero-init lazily in the host stash instead
        # (HostStash.get(..., or_zeros=True) on first boundary).
        zeros = jax.jit(
            lambda: {name: jnp.zeros(shape, jnp.float32)
                     for name, shape in shapes.items()},
            out_shardings=shardings["params"])
        out["m"], out["v"] = zeros(), zeros()
    return out


# ---------------------------------------------------------------------------
# the training step
# ---------------------------------------------------------------------------

def build_train_step(
    model: ModelDef,
    topo: MiCSTopology,
    mcfg: MiCSConfig,
    oc: OptConfig,
):
    """Returns a jitted (state, batch) -> (state, metrics) step function.

    All collectives — the per-layer hop-1 gathers and their adjoint
    reduce-scatters, and the boundary hop-2 all-reduce — are owned by one
    ``CommEngine`` constructed from (topo, mcfg).  ``policy="auto"``
    configs are first resolved by the link-model autotuner
    (core/autotune.py); pass the resolved config around if you also need
    the ranked plan.
    """
    mcfg, _ = resolve_config(mcfg, model, topo, mode="train")
    comm = CommEngine.from_config(topo, mcfg)
    boundary = plan_boundary(model, topo, mode=mcfg.boundary_schedule,
                             bucket_mb=mcfg.hop2_bucket_mb,
                             clip_mode=mcfg.clip_mode)
    ctx = L.Ctx(mode="train", tp=topo.model_size, tp_axis=MODEL_AXIS,
                compute_dtype=jnp.dtype(mcfg.gather_dtype),
                scores_bf16=mcfg.scores_bf16, mlstm_chunk=mcfg.mlstm_chunk)
    s = mcfg.micro_steps
    denom = float(s * topo.data_parallel_size)
    counters = lm.counters(model)

    def loss_of(flat, micro_batch, step_ctx):
        return lm.loss_fn(model, flat, comm, step_ctx, micro_batch)

    def sharded_step(state, batch):
        params = state["params"]
        # The step counter rides the context into every gather's VJP: the
        # int8 qgZ wires fold it into their stochastic-rounding dither key
        # (step-varying, value-independent); float wires never read it.
        step_ctx = dataclasses.replace(ctx, step_seed=state["step"])

        def micro(carry, mb):
            grads_acc, loss_acc, aux_acc, counts = carry
            (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, mb, step_ctx)
            with jax.named_scope(scopes.GRAD_ACCUM):
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
            counts = {k: counts[k] + metrics[k] for k in counts}
            return (grads_acc, loss_acc + metrics["loss"],
                    aux_acc + metrics["aux"], counts), None

        with jax.named_scope(scopes.GRAD_ACCUM):
            zeros = jax.tree.map(jnp.zeros_like, params)
        (grads, loss_sum, aux_sum, count_sums), _ = lax.scan(
            micro, (zeros, jnp.float32(0.0), jnp.float32(0.0),
                    {k: jnp.float32(0.0) for k in counters}), batch)

        # ---- boundary: hop 2 + exact clip + AdamW (core/schedule.py) ------
        # Serial reference or the bucketed software pipeline; bitwise
        # identical either way (tests/schedule_harness.py).
        new_params, new_m, new_v, gnorm = apply_boundary(
            boundary, comm, model, topo, oc, state, grads, denom,
            seed=state["step"], offload_opt=mcfg.offload_opt)
        step = state["step"]

        # The boundary's one other reduction: the step's mean loss.
        with jax.named_scope(scopes.OPTIMIZER):
            metrics = {
                "loss": lax.pmean(loss_sum / s, topo.data_axes),
                "aux": lax.pmean(aux_sum / s, topo.data_axes),
                "grad_norm": gnorm,
                **{k: lax.pmean(v / s, topo.data_axes)
                   for k, v in count_sums.items()},
            }
        new_state = {"params": new_params, "step": step + 1}
        if not mcfg.offload_opt:
            new_state["m"], new_state["v"] = new_m, new_v
        return new_state, metrics

    st_specs = state_pspecs(model, topo, offload_opt=mcfg.offload_opt)
    b_specs = batch_pspecs(model, topo)
    m_specs = {k: P() for k in ("loss", "aux", "grad_norm", *counters)}
    sharded = shard_map(
        sharded_step, mesh=topo.mesh,
        in_specs=(st_specs, b_specs),
        out_specs=(st_specs, m_specs),
        check_vma=False,
    )
    ns = lambda spec: jax.tree.map(
        lambda s_: NamedSharding(topo.mesh, s_), spec,
        is_leaf=lambda x: isinstance(x, P))
    step_fn = jax.jit(
        sharded,
        in_shardings=(ns(st_specs), ns(b_specs)),
        out_shardings=(ns(st_specs), ns(m_specs)),
        donate_argnums=(0,),
    )
    return step_fn


def make_batch_shapes(model: ModelDef, global_batch: int, seq: int,
                      micro_steps: int) -> dict[str, jax.ShapeDtypeStruct]:
    """Global abstract shapes of one training batch (for the dry-run)."""
    if global_batch % micro_steps:
        raise ValueError("global_batch must divide by micro_steps")
    b = global_batch // micro_steps
    sds = jax.ShapeDtypeStruct
    out = {
        "tokens": sds((micro_steps, b, seq), jnp.int32),
        "targets": sds((micro_steps, b, seq), jnp.int32),
        "mask": sds((micro_steps, b, seq), jnp.float32),
    }
    cfg = model.cfg
    if cfg.family == "vlm":
        out["vision"] = sds(
            (micro_steps, b, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        out["audio"] = sds(
            (micro_steps, b, cfg.n_audio_frames, cfg.d_model), jnp.bfloat16)
    return out
