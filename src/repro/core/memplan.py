"""Analytical per-device HBM footprint model (the memory planner).

MiCS's scale-aware partitioning rule (§3.1) is a *memory* rule: choose the
minimal partition group whose aggregate device memory holds the model
states, so collectives stay small and fast.  The autotuner (core/autotune)
ranks policies by predicted communication time; this module supplies the
other half of the decision — what each candidate *costs in HBM* — so the
planner can reject configurations that would OOM and implement the paper's
rule analytically (:func:`min_partition_size`).

The footprint of one training step decomposes per device into

* **arguments** — the donated state (fp32 param/m/v shards, exact by
  construction) plus the batch;
* **transients** — everything the compiled step allocates on top:
  the fp32 gradient accumulator and its loop double-buffer, the
  hop-2-reduced gradient copy, the flat-param gather buffers (x2 under
  double-buffered prefetch), the prefetch-carry backward residual, hop-2
  bucket staging, qgZ / int8-wire quantization scratch, activation
  checkpoints and the logits/CE workspace.

Every component is priced from the same static quantities the autotuner's
traffic model reads (``model.global_flat_shapes()``, the topology's
partition size / replication degree, the policies), so the two models stay
composable.  The prediction is verified against XLA's own compiled
``memory_analysis()`` on the 8-device harness — the same
predicted-vs-compiled discipline ``autotune.predict_traffic`` applies to
wire bytes (tests/memplan_harness.py; argument bytes must match exactly,
transients within :data:`MEM_RTOL`).

Calibration notes (documented tolerance): the transient model is
calibrated against the XLA *CPU* backend the harness compiles for.  Two
empirical observations are baked in: the stored prefetch carry persists
its stacked residual at fp32 (the adjoint's accumulation dtype) plus the
rotated shard copy, and the gradient accumulator is double-buffered across
the micro-step loop.  :data:`MEM_RTOL` (±35%) absorbs backend-specific
fusion and scratch variation; argument bytes carry no tolerance at all.

Degenerate cases are first-class: a single-device mesh (p = 1, nothing on
the wire, no hop 2), a partition group spanning the whole world (ZeRO-3,
no replication → no hop-2 staging), and budgets smaller than any candidate
(:class:`MemoryBudgetError`, never a silent empty plan).
"""

from __future__ import annotations

import dataclasses
import math

from repro.core.comm import GatherPolicy, SyncPolicy
from repro.core.linkmodel import GIB
from repro.core.quant import BLOCK

# Documented tolerance of the transient-footprint model vs XLA's compiled
# memory_analysis() (CPU backend; argument bytes are exact, no tolerance).
MEM_RTOL = 0.35

# bytes/element of the gathered compute buffer, per gather wire dtype (the
# int8 wire dequantizes into the bf16 compute dtype).
_COMPUTE_BYTES = {"fp32": 4, "bf16": 2, "int8": 2}
# int8 wire scratch: q payload + one f32 absmax scale per BLOCK elements.
_INT8_BYTES = 1.0 + 4.0 / BLOCK
# Per-element scratch of the qgZ hop-1 wire on the largest in-flight
# cotangent buffer.  Calibrated to the XLA CPU backend the harness verifies
# against, which does NOT fuse the threefry-dither / quantize / exchange /
# dequantize chain — ~33 full-width temporaries (u32 random bits, f32
# uniforms, block-shaped chunks, per-stage exchange copies) are live at
# once.  On accelerator backends with fused RNG this is pessimistic, which
# errs on the safe side for OOM rejection.
QGZ_SCRATCH_BYTES_PER_ELEM = 133.0


class MemoryBudgetError(ValueError):
    """No candidate fits the HBM budget (raised instead of an empty plan)."""


# KV-cache element bytes per kv_dtype (int8 adds f32 scales separately).
_KV_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}


def kv_token_bytes(model, kv_dtype: str = "bf16") -> float:
    """Per-device HBM bytes one cached token costs across all layers.

    Prices the paged KV pool (runtime/paged.py): k + v at ``kv_dtype``
    over the rank-local KV head slots, plus the per-(token, head,
    128-block) f32 scale pages of the int8 layout.  Analytic and jax-free
    — the same ``attn_dims`` the model builds its caches from.
    """
    from repro.models.dims import attn_dims

    cfg = model.cfg
    tp = max(int(getattr(model, "tp", 1)), 1)
    ad = attn_dims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                   cfg.resolved_head_dim, tp)
    per_layer = 2.0 * ad.hkv_local * ad.head_dim * _KV_BYTES[kv_dtype]
    if kv_dtype == "int8":
        per_layer += 2.0 * ad.hkv_local * math.ceil(ad.head_dim / BLOCK) * 4.0
    return per_layer * cfg.n_layers


def max_resident_requests(
    model,
    topo,
    gather: GatherPolicy,
    sync: SyncPolicy,
    *,
    hbm_bytes: float,
    ctx_len: int,
    kv_block_size: int = 16,
    kv_dtype: str = "bf16",
) -> int:
    """How many requests of ``ctx_len`` positions fit per device.

    Free HBM after the serve-mode base footprint (param shards + gather
    buffers), divided by one request's block-rounded KV bytes.  This is
    what sizes the paged pool (``MiCSConfig.max_resident_requests == 0``)
    and what the serve harness verifies against the compiled
    ``memory_analysis()`` (same discipline as the training planner).
    """
    base = predict_footprint(model, topo, gather, sync, mode="serve")
    free = float(hbm_bytes) - base.total_bytes
    blocks = math.ceil(max(ctx_len, 1) / kv_block_size)
    per_req = blocks * kv_block_size * kv_token_bytes(model, kv_dtype)
    return max(int(free // per_req), 0)


# graceful-degradation dtype order: each step right is lossier but smaller
_KV_LADDER = ("fp32", "bf16", "int8")


def degradation_levels(
    model,
    topo,
    gather: GatherPolicy,
    sync: SyncPolicy,
    *,
    hbm_bytes: float,
    ctx_len: int,
    kv_block_size: int = 16,
    kv_ceiling: str = "bf16",
    tighten: float = 0.5,
) -> list[dict]:
    """Price a graceful-degradation ladder for the serving scheduler.

    Returns ordered ``{"kv_dtype", "resident_cap", "label"}`` levels for
    :class:`repro.runtime.batching.DegradationLadder` (plain dicts — core
    must not import runtime):

    - level 0: the configured operating point — ``kv_ceiling`` KV at the
      full :func:`max_resident_requests` residency;
    - level 1: same dtype, residency tightened by ``tighten`` — fewer
      residents means fewer evictions and less replayed work under
      ``reserve="min"`` thrash;
    - level 2+: one lossier KV dtype per level (bf16 → int8), each priced
      at its own (larger) planner residency, again tightened.

    Every cap is at least 1, so the ladder degrades throughput and
    numerics but can never deadlock admission.
    """
    if kv_ceiling not in _KV_LADDER:
        raise ValueError(f"unknown kv dtype {kv_ceiling!r}")
    if not 0.0 < tighten <= 1.0:
        raise ValueError("tighten must be in (0, 1]")

    def cap(dt):
        return max_resident_requests(
            model, topo, gather, sync, hbm_bytes=hbm_bytes, ctx_len=ctx_len,
            kv_block_size=kv_block_size, kv_dtype=dt)

    r0 = cap(kv_ceiling)
    levels = [
        {"kv_dtype": kv_ceiling, "resident_cap": max(r0, 1),
         "label": "configured"},
        {"kv_dtype": kv_ceiling, "resident_cap": max(int(r0 * tighten), 1),
         "label": "tightened"},
    ]
    for dt in _KV_LADDER[_KV_LADDER.index(kv_ceiling) + 1:]:
        levels.append({"kv_dtype": dt,
                       "resident_cap": max(int(cap(dt) * tighten), 1),
                       "label": f"kv_{dt}"})
    return levels


@dataclasses.dataclass(frozen=True)
class DeviceGrid:
    """The three sizes the footprint model needs — duck-types MiCSTopology
    so the planner runs device-free (partition-group auto-sizing iterates
    these without building meshes)."""

    partition_size: int
    replication_degree: int = 1


@dataclasses.dataclass(frozen=True)
class MemPlan:
    """Predicted per-device HBM footprint of one step."""

    components: dict           # transient component -> bytes
    args_bytes: float          # donated state + batch (exact)
    mode: str

    @property
    def temp_bytes(self) -> float:
        return float(sum(self.components.values()))

    @property
    def total_bytes(self) -> float:
        return self.args_bytes + self.temp_bytes

    @property
    def total_gb(self) -> float:
        return self.total_bytes / GIB

    def describe(self) -> dict:
        return {
            "args_bytes": self.args_bytes,
            "temp_bytes": self.temp_bytes,
            "total_bytes": self.total_bytes,
            "total_gib": self.total_gb,
            "components": dict(self.components),
            "mode": self.mode,
        }


def _pool_shapes(model) -> dict:
    return model.global_flat_shapes()


def predict_footprint(
    model,
    topo,
    gather: GatherPolicy,
    sync: SyncPolicy,
    *,
    micro_steps: int = 1,
    mode: str = "train",
    local_batch: int = 0,
    seq: int = 0,
    boundary: str = "bucketed",
    hop2_bucket_mb: float = 32.0,
    kv_pages_tokens: int = 0,
    kv_dtype: str = "bf16",
    decode_batch: int = 0,
    decode_ctx: int = 0,
    decode_chunk: int = 0,
    kv_max_blocks: int = 0,
) -> MemPlan:
    """Per-device HBM footprint of one training/serving step.

    ``topo`` needs only ``partition_size`` and ``replication_degree``
    (:class:`DeviceGrid` suffices).  ``local_batch``/``seq`` size the
    activation-checkpoint and logits terms; pass 0 to price model states
    and communication buffers only (what ``resolve_config`` does — the
    dry-run passes the real shapes).  All byte counts are per device.
    """
    p = max(int(topo.partition_size), 1)
    repl = max(int(getattr(topo, "replication_degree", 1)), 1)
    cb = _COMPUTE_BYTES[gather.wire_dtype]
    shapes = _pool_shapes(model)
    scanned = {pl.name for pl in model.pools}
    train = mode == "train"

    shard4 = {name: stack * math.ceil(flat_len / p) * 4
              for name, (stack, _tp, flat_len) in shapes.items()}
    s4 = float(sum(shard4.values()))          # one fp32 state copy / device

    # -- arguments (exact): fp32 params + m + v shards, step scalar, batch --
    args = 3.0 * s4 + 4.0 if train else s4
    if train and local_batch and seq:
        # tokens + targets (int32) + mask (f32), stacked over micro-steps
        args += micro_steps * local_batch * seq * 12.0

    comp: dict[str, float] = {}

    def add(name: str, nbytes: float):
        if nbytes > 0:
            comp[name] = comp.get(name, 0.0) + float(nbytes)

    # -- gather buffers: the full flat buffer per pool being applied -------
    prefetching = gather.prefetch
    max_flat = 0
    for name, (stack, _tp, flat_len) in shapes.items():
        max_flat = max(max_flat, flat_len)
        nbuf = 2 if (prefetching and name in scanned and stack > 1) else 1
        add("gather_buffers", flat_len * cb * nbuf)
    if gather.wire_dtype == "int8" and p > 1:
        # in-flight (q, scales) payloads of the largest gather
        add("int8_wire_scratch", 2 * max_flat * _INT8_BYTES)
    if gather.topology == "outer_first" and p > 1:
        add("reorder_copy", max_flat * cb)

    if not train:
        if local_batch and seq:
            for name, (stack, _tp, flat_len) in shapes.items():
                if name in scanned and getattr(model, "cfg", None):
                    add("activation_ckpt",
                        stack * local_batch * seq * model.cfg.d_model * cb)
        # paged-KV serving (runtime/paged.py): the block pool is a donated
        # argument like the param shards, exact by construction; the decode
        # step's transients are the per-layer gathered [b, MB*bs, h, dh]
        # k/v views plus the sampling logits workspace.
        if kv_pages_tokens:
            pool = kv_pages_tokens * kv_token_bytes(model, kv_dtype)
            args += pool
            # the decode scan double-buffers the donated pool carry; pools
            # stored narrower than fp32 additionally stage their f32
            # upcast during the write/read fusion (observed on the XLA
            # buffer ledger, held to MEM_RTOL by the serve harness)
            add("kv_pool_update", pool)
            if kv_dtype != "fp32":
                add("kv_pool_update",
                    kv_pages_tokens * kv_token_bytes(model, "fp32"))
        if decode_batch and decode_chunk:
            # the scheduler's fixed-shape plan rows (runtime/batching
            # StepPlan): tokens [b, chunk] + block table [b, max_blocks]
            # + pos/n_new/seeds (int32) + temps (f32) — donated-arg peers
            # of the KV pool, 4 bytes each.
            args += decode_batch * (decode_chunk + kv_max_blocks + 4) * 4.0
        if decode_batch and decode_ctx and getattr(model, "cfg", None):
            from repro.models.dims import attn_dims

            mcfg_ = model.cfg
            tp = max(int(getattr(model, "tp", 1)), 1)
            ad = attn_dims(mcfg_.d_model, mcfg_.n_heads, mcfg_.n_kv_heads,
                           mcfg_.resolved_head_dim, tp)
            view = 2.0 * decode_batch * decode_ctx * ad.hkv_local \
                * ad.head_dim * cb
            if kv_dtype == "int8":   # dequantize reads q + f32 scales too
                view += 2.0 * decode_batch * decode_ctx * ad.hkv_local \
                    * (ad.head_dim + math.ceil(ad.head_dim / BLOCK) * 4)
            add("kv_gather_view", view)
            vocab = int(getattr(model, "vocab_padded", mcfg_.vocab))
            add("decode_logits", decode_batch * (vocab // tp) * 8)
        return MemPlan(components=comp, args_bytes=args, mode=mode)

    # -- gradient accumulator + its micro-loop double buffer ---------------
    add("grad_accum", s4)
    add("grad_loop_buffer", s4)
    # -- the hop-2-reduced fp32 gradient copy the boundary materializes ----
    add("boundary_reduced", s4)
    # -- backward: the largest full-buffer cotangent (fp32 adjoint input) --
    add("gather_adjoint", max_flat * 4)

    # -- prefetch-carry backward residual, per pool route (models/lm.py) --
    # remat: one transient re-gathered buffer (its forward reads the
    # lookahead row in place).  stored (enc-dec decoder pools, which read
    # the encoder output): the stacked carried buffer persists at fp32
    # (observed: the adjoint accumulation dtype) + the rotated shard copy.
    from repro.models.lm import train_route

    cfg = getattr(model, "cfg", None)
    for name, (stack, _tp, flat_len) in shapes.items():
        if name not in scanned:
            continue
        route = train_route(cfg, name, stack, gather)
        if route == "serial":
            continue
        rolled = stack * math.ceil(flat_len / p) * 4
        if route == "stored":
            add("prefetch_carry", stack * flat_len * 4 + rolled)
        else:
            add("prefetch_carry", flat_len * cb)

    # -- activation checkpoints + logits/CE workspace ----------------------
    if local_batch and seq and cfg is not None:
        for name, (stack, _tp, flat_len) in shapes.items():
            if name in scanned:
                add("activation_ckpt",
                    stack * local_batch * seq * cfg.d_model * cb)
        tp = max(int(getattr(model, "tp", 1)), 1)
        vocab = int(getattr(model, "vocab_padded", cfg.vocab))
        add("logits_ce", local_batch * seq * (vocab // tp) * 8)
        if getattr(cfg, "moe_layers", 0):
            # one expert layer's dropless buffers: the tokens' top-k rows in
            # expert order and their outputs [n*k, d], the gate and up
            # products and their product [n*k, f] (models/blocks.moe_routed)
            add("expert_rows", local_batch * seq * cfg.top_k
                * (2 * cfg.d_model + 3 * cfg.d_ff) * cb)

    # -- hop-2 staging (replication-group boundary) ------------------------
    if repl > 1 and sync.mode == "2hop":
        max_shard4 = max(shard4.values())
        eff = max_shard4 if boundary == "serial" \
            else min(hop2_bucket_mb * 1e6, max_shard4)
        add("hop2_staging", 2 * eff)
        if sync.hop2_wire_dtype == "int8":
            add("hop2_qgz_scratch", 2 * eff / 4 * _INT8_BYTES)

    # -- qgZ hop-1 scratch --------------------------------------------------
    if sync.hop1_wire_dtype == "int8" and p > 1:
        add("qgz_scratch", max_flat * QGZ_SCRATCH_BYTES_PER_ELEM)

    return MemPlan(components=comp, args_bytes=args, mode=mode)


# ---------------------------------------------------------------------------
# scale-aware partition-group auto-sizing (the paper's §3.1 rule)
# ---------------------------------------------------------------------------

def partition_size_candidates(data_extent: int) -> list[int]:
    """Partition-group sizes a data axis of ``data_extent`` admits,
    ascending — every divisor, so the minimal fitting one is exact."""
    if data_extent < 1:
        raise ValueError(f"data_extent must be >= 1, got {data_extent}")
    return [d for d in range(1, data_extent + 1) if data_extent % d == 0]


def min_partition_size(
    model,
    *,
    data_extent: int,
    hbm_budget_gb: float,
    gather: GatherPolicy = GatherPolicy(),
    sync: SyncPolicy = SyncPolicy(),
    micro_steps: int = 1,
    mode: str = "train",
    local_batch: int = 0,
    seq: int = 0,
    boundary: str = "bucketed",
    hop2_bucket_mb: float = 32.0,
    extra_replication: int = 1,
) -> tuple[int, MemPlan]:
    """The paper's scale-aware partitioning rule, analytically.

    Walks partition-group sizes ascending (divisors of ``data_extent`` —
    the mesh axis the partition group is carved from) and returns the
    first ``(p, plan)`` whose predicted per-device footprint fits
    ``hbm_budget_gb`` GiB — the *minimal* group that fits.
    ``extra_replication`` multiplies the replication degree for
    data-parallel axes the group cannot span (the pod axis of a multi-pod
    mesh, the dp2 leftover of tp < model axis) so hop-2 staging is priced
    even when p == data_extent.  Raises
    :class:`MemoryBudgetError` when even the whole data axis (ZeRO-3
    scale) does not fit — never a silent empty plan.
    """
    budget = float(hbm_budget_gb) * GIB
    best = None
    for p in partition_size_candidates(data_extent):
        grid = DeviceGrid(
            partition_size=p,
            replication_degree=(data_extent // p) * max(extra_replication, 1))
        plan = predict_footprint(
            model, grid, gather, sync, micro_steps=micro_steps, mode=mode,
            local_batch=local_batch, seq=seq, boundary=boundary,
            hop2_bucket_mb=hop2_bucket_mb)
        if best is None or plan.total_bytes < best[1].total_bytes:
            best = (p, plan)
        if plan.total_bytes <= budget:
            return p, plan
    assert best is not None
    raise MemoryBudgetError(
        f"no partition group fits hbm_budget_gb={hbm_budget_gb}: the "
        f"smallest candidate (p={best[0]}) "
        f"needs {best[1].total_gb:.3f} GiB per device "
        f"(args {best[1].args_bytes / GIB:.3f} + "
        f"temp {best[1].temp_bytes / GIB:.3f}); raise the budget, shrink "
        f"the model, or grow the mesh")
