"""Boundary scheduler: the gradient-accumulation boundary as a bucketed
software pipeline (hop-2 overlap, ROADMAP "Async hop-2 overlap").

The boundary of one training step is ``hop-2 all-reduce -> global-norm clip
-> AdamW`` (paper §3.4: the expensive cross-replica sync runs once per
accumulation boundary).  The seed implementation ran it as a monolithic
barrier: every pool's hop-2 completed before a single optimizer FLOP
issued.  This module refactors the boundary into a **plan + two schedules**:

* :func:`plan_boundary` partitions each pool's local gradient shard
  (``[stack, 1, shard_len]`` fp32) into fixed-byte buckets
  (``core/flat_param.partition_buckets``) in one canonical order — pools in
  ``model.all_pools()`` order, offsets ascending.  Bucket count is a
  compile-time property of ``(model, topo, hop2_bucket_mb)``.
* ``serial`` schedule (:func:`apply_boundary`, the reference path): hop-2
  the whole gradient tree first, then compute, exactly like the seed.
* ``bucketed`` schedule: a software pipeline over the plan's buckets —
  bucket *k*'s hop-2 collective (``CommEngine.hop2_bucketed``) is issued
  *before* bucket *k−1*'s dependent compute (squared-norm partial, bf16
  wire decompress), so the collective has no data dependency on that
  compute and XLA's scheduler can overlap the two.  Once the clip scale is
  known the AdamW shard update runs per pool with the scale folded in.

**The exact-clip ordering argument.**  Global-norm clipping needs the norm
of *every* gradient element before *any* update applies, so the AdamW pass
can never overlap the last bucket's hop-2 — but everything before it can.
To keep the two schedules bitwise identical at every bucket size, both
compute the squared norm the same way: a left-fold over per-bucket partials
in the plan's canonical order (the serial path folds over slices of the
pool-wise-reduced buffer; the bucketed path over the bucket-wise-reduced
buffers — elementwise ``psum``/casts commute with slicing, so the inputs
are bitwise equal, and the fold order is literally the same Python loop).
The denominator (``micro_steps * data_parallel``) and the clip factor are
folded into one ``grad_scale`` passed to ``adamw_shard_update`` — no
standalone full-gradient-tree division pass on either schedule.

**The approximate-clip pipeline** (``clip_mode="approx"``).  The exact
clip's single barrier — no update before the complete norm — is the last
serially-exposed dependency of the boundary.  Approx mode removes it:
bucket *k*'s AdamW shard update runs under bucket *k+1*'s in-flight hop-2
using the **running** squared norm through bucket *k−1* (a one-bucket-
stale clip factor), so the whole boundary becomes one software pipeline
``issue hop-2(k) → AdamW(k−1, stale norm) → fold psum(k−1)`` with no
global barrier.  The drain step folds the final bucket's partial *first*,
so the last bucket (and the reported ``grad_norm`` metric) sees the
complete norm.  Degenerate guarantees: a one-bucket plan's only update is
the drain's complete-norm update — the exact schedule's ordering; and
whenever the clip is inactive (``gnorm <= clip_norm`` at every prefix —
e.g. a huge ``clip_norm``), every prefix factor is exactly 1.0 and the
update arithmetic is element-for-element the exact path's: the loss and
``grad_norm`` trajectories are bitwise identical at any bucket count, and
parameters agree to the final ulp (the pipelined program fuses the
elementwise AdamW chain differently, so XLA may round its last op
differently — tests/schedule_harness.py pins the tolerance).

*Divergence bound.*  The running norm is a prefix of the full sum, so
``gnorm_k <= gnorm`` and the stale factor ``c_k = min(1, C/gnorm_k)``
over-estimates the exact ``c = min(1, C/gnorm)``: each bucket's applied
gradient is the exact one scaled by ``c_k/c ∈ [1, gnorm/gnorm_k]`` — the
update direction per bucket is unchanged, only under-clipped, and the
applied step magnitude stays bounded by the Adam trust region (the
update is ``lr``-bounded elementwise regardless of ``grad_scale``).  The
discrepancy is largest for bucket 0 (factor ``min(1, C/gnorm)^-1``,
clamped to 1 whenever clipping is inactive) and vanishes as the prefix
grows; a tiny-LM convergence smoke (tests/schedule_harness.py) bounds the
end-to-end effect — final loss within ``APPROX_CLIP_LOSS_RTOL`` of the
exact reference with clipping engaged.

**Host-offloaded optimizer shards** (``offload_opt=True``).  The AdamW
``m``/``v`` shards are touched exactly once per boundary, so both
schedules can stream them from host memory around the update
(core/hostoffload.py: ordered-io_callback d2h/h2d stash, lazily
zero-initialized) instead of keeping them HBM-resident — the state dict
then carries only ``params``/``step`` and the memory planner subtracts
``2 × 4`` bytes/element from the per-device footprint.  The params
trajectory is bitwise unchanged (the fetched moments are bitwise the
stored ones).

**The int8 decompress leg** (qgZ follow-on).  With
``SyncPolicy.hop2_wire_dtype='int8'`` each hop-2 payload runs as a
block-quantized all-reduce (``collectives.quantized_all_reduce``: int8 +
f32 scales on both legs, fp32 accumulation between them), and the hidden
per-bucket compute grows the block *dequantize* on top of the norm
partial.  Unlike the elementwise bf16 cast, the quantization blocks follow
the payload, so int8 hop-2 results depend on payload granularity: serial
and bucketed agree to quantization error, not bitwise — the bitwise
schedule-equivalence guarantee above is for the fp32/bf16 wires.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes
from repro.core.flat_param import partition_buckets
from repro.core.hostoffload import TAG_M, TAG_V
from repro.core.topology import MODEL_AXIS, MiCSTopology
from repro.optim.adamw import OptConfig, adamw_shard_update

BOUNDARY_SCHEDULES = ("serial", "bucketed")
CLIP_MODES = ("exact", "approx")

# Convergence-smoke tolerance of the approx clip: the tiny-LM final loss
# must sit within this relative tolerance of the exact reference
# (tests/schedule_harness.py::approx_convergence — the documented bound).
APPROX_CLIP_LOSS_RTOL = 0.05

# fp32 gradient accumulator bytes per element — what a bucket's byte budget
# is measured in (the wire payload may be narrower under bf16 hop-2).
GRAD_ITEMSIZE = 4


@dataclasses.dataclass(frozen=True)
class BucketRef:
    """One bucket: a static ``[lo, hi)`` slice of ``pool``'s flattened
    local gradient shard."""

    pool: str
    lo: int
    hi: int

    @property
    def elems(self) -> int:
        return self.hi - self.lo


@dataclasses.dataclass(frozen=True)
class BoundaryPlan:
    """Static schedule of one gradient-accumulation boundary."""

    mode: str                          # 'serial' | 'bucketed'
    bucket_mb: float
    shard_elems: dict                  # pool -> local grad elements
    buckets: tuple                     # BucketRef, canonical order
    clip_mode: str = "exact"           # 'exact' barrier | 'approx' pipeline

    def __post_init__(self):
        if self.mode not in BOUNDARY_SCHEDULES:
            raise ValueError(f"unknown boundary schedule {self.mode!r} "
                             f"(expected one of {BOUNDARY_SCHEDULES})")
        if self.clip_mode not in CLIP_MODES:
            raise ValueError(f"unknown clip_mode {self.clip_mode!r} "
                             f"(expected one of {CLIP_MODES})")
        if self.clip_mode == "approx" and self.mode != "bucketed":
            raise ValueError(
                "clip_mode='approx' requires the bucketed boundary schedule "
                "(the serial reference has no bucket pipeline to hide the "
                "optimizer under)")

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def pool_buckets(self, pool: str) -> list:
        return [b for b in self.buckets if b.pool == pool]

    def hop2_payload_elems(self) -> list:
        """Element counts of the hop-2 collectives this plan issues, in
        order: one whole-pool payload per pool under ``serial``, one per
        bucket under ``bucketed``.  The single source of truth shared by
        the executor (:func:`apply_boundary`), the cost model
        (``autotune.cost_hop2_schedule``) and the census cross-checks
        (``dryrun``'s ``bucket_count_match``)."""
        if self.mode == "serial":
            return list(self.shard_elems.values())   # all_pools() order
        return [b.elems for b in self.buckets]

    @property
    def n_hop2_collectives(self) -> int:
        return len(self.hop2_payload_elems())

    def describe(self) -> dict:
        """Static record for dry-run artifacts / BENCH json."""
        per_pool = {}
        for b in self.buckets:
            per_pool[b.pool] = per_pool.get(b.pool, 0) + 1
        return {
            "mode": self.mode,
            "clip_mode": self.clip_mode,
            "bucket_mb": self.bucket_mb,
            "n_buckets": self.n_buckets,
            "n_hop2_collectives": self.n_hop2_collectives,
            "buckets_per_pool": per_pool,
            "max_bucket_bytes": max(
                (b.elems * GRAD_ITEMSIZE for b in self.buckets), default=0),
        }


def plan_boundary(model, topo: MiCSTopology, *, mode: str,
                  bucket_mb: float, clip_mode: str = "exact") -> BoundaryPlan:
    """Bucketize every pool's local gradient shard into fixed-byte buckets.

    The same plan backs both schedules: the serial reference uses it only
    to order the squared-norm partials (so it stays bitwise comparable to
    the bucketed pipeline at any bucket size), the bucketed schedule
    additionally issues one hop-2 collective per bucket.  ``clip_mode``
    selects the exact global-norm-clip barrier (the reference) or the
    approximate one-bucket-stale clip pipeline (module docstring).
    """
    p = topo.partition_size
    shard_elems = {}
    buckets = []
    for pool in model.all_pools():
        stack, _tp, flat_len = model.global_flat_shapes()[pool.name]
        n = stack * (flat_len // p)
        shard_elems[pool.name] = n
        for lo, hi in partition_buckets(n, bucket_mb, GRAD_ITEMSIZE):
            buckets.append(BucketRef(pool.name, lo, hi))
    return BoundaryPlan(mode=mode, bucket_mb=float(bucket_mb),
                        shard_elems=shard_elems, buckets=tuple(buckets),
                        clip_mode=clip_mode)


def _sq(bucket: jax.Array) -> jax.Array:
    """One bucket's squared-norm partial (fp32)."""
    return jnp.sum(jnp.square(bucket))


def _reduce_serial(plan: BoundaryPlan, comm, flat_grads: dict, seed=None):
    """Reference: whole-pool hop-2 first, then per-bucket norm partials.

    ``salt`` (the pool index) seeds the int8 hop-2 wire's stochastic-
    rounding dither per payload and ``seed`` (the traced step counter)
    decorrelates it across steps; the float wires ignore both.
    """
    reduced = {name: comm.hop2(g, salt=i, seed=seed)
               for i, (name, g) in enumerate(flat_grads.items())}
    sq_parts = [
        _sq(lax.slice_in_dim(reduced[b.pool], b.lo, b.hi, axis=0))
        for b in plan.buckets
    ]
    return reduced, sq_parts


def _reduce_bucketed(plan: BoundaryPlan, comm, flat_grads: dict, seed=None):
    """Software pipeline: issue bucket k's hop-2, then run bucket k−1's
    dependent compute (squared-norm partial + wire decompress — the bf16
    upcast, or the int8 leg's block dequantize).  The collective of bucket
    k has no data dependency on bucket k−1's compute, which is what lets
    the backend overlap the two; the drain step handles the last bucket.
    The global bucket index salts the int8 wire's dither so no two
    payloads of one boundary share a key (offsets repeat across pools —
    every pool has a bucket at lo=0 — so the plan-order index is the salt).
    """
    parts: dict[str, list] = {name: [] for name in flat_grads}
    sq_parts: list[jax.Array] = []
    pending = None  # (BucketRef, in-flight reduced bucket)

    def retire(ref, reduced_bucket):
        sq_parts.append(_sq(reduced_bucket))
        parts[ref.pool].append(reduced_bucket)

    for i, ref in enumerate(plan.buckets):
        raw = lax.slice_in_dim(flat_grads[ref.pool], ref.lo, ref.hi, axis=0)
        in_flight = comm.hop2_bucketed(raw, salt=i, seed=seed)  # bucket k
        if pending is not None:
            retire(*pending)                  # compute for bucket k−1
        pending = (ref, in_flight)
    if pending is not None:
        retire(*pending)

    reduced = {
        name: (jnp.concatenate(bufs) if len(bufs) > 1 else bufs[0])
        for name, bufs in parts.items() if bufs
    }
    return reduced, sq_parts


def _bucket_masks(pool, ref: BucketRef, shard_coord, shard_len: int):
    """Decay/padding masks for one bucket of a pool's flattened shard.

    The flattened ``[stack * shard_len]`` buffer broadcasts the per-shard
    layout masks over stack rows, so flat index ``f`` maps to layout
    position ``shard_coord*shard_len + (f % shard_len)`` — these are
    exactly slices of ``decay_mask_for_shard``/``padding_mask_for_shard``,
    which keeps the per-bucket AdamW bitwise equal to the sliced full-shard
    update.
    """
    local = (ref.lo + jnp.arange(ref.elems, dtype=jnp.int32)) % shard_len
    gidx = shard_coord * shard_len + local
    dm = jnp.ones((ref.elems,), jnp.float32)
    for lo, hi in pool.layout.nodecay_ranges():
        if lo >= hi:
            continue
        dm = jnp.where((gidx >= lo) & (gidx < hi), 0.0, dm)
    pm = (gidx < pool.layout.raw_len).astype(jnp.float32)
    return dm, pm


def _apply_boundary_approx(plan, comm, model, topo, oc, state, grads,
                           denom, seed, offload_opt):
    """The approximate-clip software pipeline (module docstring).

    Per plan-order bucket *i*: issue bucket *i*'s hop-2, then (while it is
    in flight) run bucket *i−1*'s AdamW with the clip factor from the
    running squared norm through bucket *i−2*, then fold bucket *i−1*'s
    psum into the running norm.  The drain folds the final bucket's psum
    *before* its update, so the last bucket uses the complete norm, and a
    one-bucket plan reduces to the exact path's ordering.  The returned
    ``grad_norm`` metric is accumulated by the exact path's canonical
    local left-fold + single psum, so the metric is bitwise identical to
    the exact schedule's at any bucket count — only the *applied* clip
    factors are stale.
    """
    flat_grads = {name: grads[name].reshape(-1) for name in plan.shard_elems}
    shard_coord = comm.partition_coord()
    pools = {p.name: p for p in model.all_pools()}
    norm_axes = topo.partition_axes + (MODEL_AXIS,)
    stash = comm.host_stash if offload_opt else None

    flat_state = {}
    for name in plan.shard_elems:
        flat_state[name] = {
            "p": state["params"][name].reshape(-1),
            "m": None if offload_opt else state["m"][name].reshape(-1),
            "v": None if offload_opt else state["v"][name].reshape(-1),
            "shard_len": grads[name].shape[-1],
        }
    out = {name: {"p": [], "m": [], "v": []} for name in plan.shard_elems}

    def update(i, ref, g_bucket, running_sq):
        """Bucket ``ref``'s AdamW with the clip factor from ``running_sq``
        (the stale prefix norm — or the complete one at the drain)."""
        fs = flat_state[ref.pool]
        gnorm_i = jnp.sqrt(running_sq) / denom
        clip = jnp.minimum(1.0, oc.clip_norm / jnp.maximum(gnorm_i, 1e-12))
        grad_scale = clip / denom
        p_in = lax.slice_in_dim(fs["p"], ref.lo, ref.hi, axis=0)
        if offload_opt:
            m_in = stash.get(TAG_M, i, (ref.elems,), jnp.float32,
                             or_zeros=True, ordered=False)
            v_in = stash.get(TAG_V, i, (ref.elems,), jnp.float32,
                             or_zeros=True, ordered=False)
        else:
            m_in = lax.slice_in_dim(fs["m"], ref.lo, ref.hi, axis=0)
            v_in = lax.slice_in_dim(fs["v"], ref.lo, ref.hi, axis=0)
        dm, pm = _bucket_masks(pools[ref.pool], ref, shard_coord,
                               fs["shard_len"])
        p_new, m_new, v_new = adamw_shard_update(
            p_in, g_bucket, m_in, v_in, state["step"], oc,
            decay_mask=dm, pad_mask=pm, grad_scale=grad_scale)
        out[ref.pool]["p"].append(p_new)
        if offload_opt:
            # Unordered: the put operand depends on the get via the AdamW
            # update, so dataflow already sequences the pair; ordered
            # callbacks here deadlock against the hop-2 psum rendezvous on
            # the multi-device CPU runtime.  The tokens MUST reach the
            # computation's outputs (folded into gnorm below): a put whose
            # token is dropped stalls the runtime the same way.
            put_toks.append(stash.put(TAG_M, i, m_new, ordered=False))
            put_toks.append(stash.put(TAG_V, i, v_new, ordered=False))
        else:
            out[ref.pool]["m"].append(m_new)
            out[ref.pool]["v"].append(v_new)

    running_sq = jnp.float32(0.0)
    sq_local = jnp.float32(0.0)   # exact path's canonical left-fold — the
    #                               returned metric is bitwise identical to
    #                               the exact schedule's grad_norm
    put_toks = []
    pending = None  # (bucket index, BucketRef, in-flight reduced bucket)
    for i, ref in enumerate(plan.buckets):
        raw = lax.slice_in_dim(flat_grads[ref.pool], ref.lo, ref.hi, axis=0)
        in_flight = comm.hop2_bucketed(raw, salt=i, seed=seed)
        if pending is not None:
            j, pref, pbucket = pending
            update(j, pref, pbucket, running_sq)   # stale: through bucket j-1
            running_sq = running_sq + lax.psum(_sq(pbucket), norm_axes)
            sq_local = sq_local + _sq(pbucket)
        pending = (i, ref, in_flight)
    if pending is not None:  # drain: complete norm for the final bucket
        j, pref, pbucket = pending
        running_sq = running_sq + lax.psum(_sq(pbucket), norm_axes)
        sq_local = sq_local + _sq(pbucket)
        update(j, pref, pbucket, running_sq)

    gnorm = jnp.sqrt(lax.psum(sq_local, norm_axes)) / denom
    if put_toks:    # keep the d2h puts live (value is always 0)
        gnorm = gnorm + sum(put_toks).astype(jnp.float32) * 0.0

    new_params, new_m, new_v = {}, {}, {}
    for name in plan.shard_elems:
        shape = grads[name].shape

        def cat(bufs, shape=shape):
            return (jnp.concatenate(bufs) if len(bufs) > 1
                    else bufs[0]).reshape(shape)

        if not out[name]["p"]:         # empty pool: nothing to update
            new_params[name] = state["params"][name]
            if not offload_opt:
                new_m[name] = state["m"][name]
                new_v[name] = state["v"][name]
            continue
        new_params[name] = cat(out[name]["p"])
        if not offload_opt:
            new_m[name] = cat(out[name]["m"])
            new_v[name] = cat(out[name]["v"])
    return new_params, new_m, new_v, gnorm


@jax.named_scope(scopes.OPTIMIZER)
def apply_boundary(
    plan: BoundaryPlan,
    comm,
    model,
    topo: MiCSTopology,
    oc: OptConfig,
    state: dict,
    grads: dict,
    denom: float,
    seed=None,
    offload_opt: bool = False,
):
    """Run one gradient-accumulation boundary under ``plan``.

    ``grads`` holds per-pool fp32 accumulated gradient *sums* (local shards,
    ``[stack, 1, shard_len]``); ``denom`` is the mean divisor
    (``micro_steps * data_parallel``).  Returns
    ``(new_params, new_m, new_v, grad_norm)``.  Under
    ``plan.clip_mode='exact'`` the global-norm clip is a barrier — the norm
    is reduced from every bucket's partial before any shard update issues;
    ``'approx'`` pipelines each bucket's update under the next bucket's
    hop-2 with a one-bucket-stale clip factor (module docstring).  ``seed``
    (the traced step counter) feeds the int8 hop-2 wire's stochastic-
    rounding dither; float wires ignore it.  ``offload_opt=True`` streams
    the AdamW ``m``/``v`` shards through the host stash (lazy zero-init)
    instead of the state dict — ``new_m``/``new_v`` come back empty and the
    params trajectory is bitwise unchanged.
    """
    if plan.mode == "bucketed" and plan.clip_mode == "approx":
        return _apply_boundary_approx(plan, comm, model, topo, oc, state,
                                      grads, denom, seed, offload_opt)
    flat_grads = {
        name: grads[name].reshape(-1) for name in plan.shard_elems
    }
    if plan.mode == "bucketed":
        reduced, sq_parts = _reduce_bucketed(plan, comm, flat_grads, seed)
    else:
        reduced, sq_parts = _reduce_serial(plan, comm, flat_grads, seed)

    # ---- exact global-norm clip, denominator folded -----------------------
    sq_local = jnp.float32(0.0)
    for part in sq_parts:               # fixed left-fold, canonical order
        sq_local = sq_local + part
    sq = lax.psum(sq_local, topo.partition_axes + (MODEL_AXIS,))
    gnorm = jnp.sqrt(sq) / denom
    clip = jnp.minimum(1.0, oc.clip_norm / jnp.maximum(gnorm, 1e-12))
    grad_scale = clip / denom           # mean + clip in one fused factor

    # ---- AdamW on fp32 shards, clip scale folded in -----------------------
    shard_coord = comm.partition_coord()
    stash = comm.host_stash if offload_opt else None
    new_params, new_m, new_v = {}, {}, {}
    put_toks = []
    for pool_idx, pool in enumerate(model.all_pools()):
        name = pool.name
        g = reduced[name].reshape(grads[name].shape)
        shard_len = g.shape[-1]
        start = shard_coord * shard_len
        dm = pool.layout.decay_mask_for_shard(start, shard_len)
        pm = pool.layout.padding_mask_for_shard(start, shard_len)
        if offload_opt:
            m_in = stash.get(TAG_M, pool_idx, g.shape, jnp.float32,
                             or_zeros=True, ordered=False)
            v_in = stash.get(TAG_V, pool_idx, g.shape, jnp.float32,
                             or_zeros=True, ordered=False)
        else:
            m_in, v_in = state["m"][name], state["v"][name]
        p, m, v = adamw_shard_update(
            state["params"][name], g, m_in, v_in,
            state["step"], oc, decay_mask=dm, pad_mask=pm,
            grad_scale=grad_scale)
        new_params[name] = p
        if offload_opt:
            # Unordered: dataflow (get -> AdamW -> put) sequences the pair;
            # tokens fold into gnorm to stay live (_apply_boundary_approx).
            put_toks.append(stash.put(TAG_M, pool_idx, m, ordered=False))
            put_toks.append(stash.put(TAG_V, pool_idx, v, ordered=False))
        else:
            new_m[name], new_v[name] = m, v
    if put_toks:
        gnorm = gnorm + sum(put_toks).astype(jnp.float32) * 0.0
    return new_params, new_m, new_v, gnorm
