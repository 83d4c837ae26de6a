"""CommEngine: the single construction point for every MiCS collective.

The paper's win comes from *who* talks (partition groups of size p, §3.2) and
*how* they talk (hierarchical staging §3.3, coalesced flat buffers §4,
two-hop gradient sync §3.4).  Before this module those policy decisions were
smeared across ``collectives.py``, ``mics.py``, ``quant.py`` and
``serving.py`` as ad-hoc flags; here they are one object:

* :class:`GatherPolicy` — per-pool choice of collective **topology**
  (``flat`` single collective / ``inner_first`` 2-stage / ``outer_first``
  paper-faithful 3-stage), **wire dtype** (``fp32`` / ``bf16`` / ``int8``
  blockwise-quantized à la ZeRO++ qwZ — subsuming the old serving-only
  ``quant.py`` path), and the **double-buffered prefetch schedule** (layer
  i+1's all-gather issued during layer i's compute).
* :class:`SyncPolicy` — hop-1 adjoint mode (exact staged reduce-scatter vs
  the Fig-14 ``allreduce_slice`` ablation), the hop-1 wire dtype (``fp32``
  exact / ``bf16`` / ``int8`` ZeRO++-qgZ-style per-stage block-quantized
  reduce-scatter with fp32 inter-stage accumulation), and hop-2 wire
  compression (``fp32`` / ``bf16`` / ``int8`` quantized all-reduce).
* :class:`CommEngine` — binds the policies to a :class:`MiCSTopology` and
  owns the **centralized custom-VJP machinery**: each forward gather policy
  is paired with its *exact* adjoint reduce-scatter
  (``collectives.hierarchical_reduce_scatter`` mirrors the gather stages in
  reverse), so hop-1 gradient synchronization materializes identically for
  every topology/wire combination from plain ``jax.grad``.

Consumers (``mics.build_train_step``, ``runtime/serving.py``,
``launch/dryrun.py``, ``benchmarks``) construct a CommEngine from
``MiCSConfig``/``MiCSTopology`` via :meth:`CommEngine.from_config` and never
touch raw collectives again.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import scopes
from repro.core import collectives as C
from repro.core import quant as Q
from repro.core.flat_param import model_gather_fn_for
from repro.core.topology import MODEL_AXIS, MiCSTopology, hierarchy_factors

GATHER_TOPOLOGIES = ("flat", "inner_first", "outer_first")
WIRE_DTYPES = ("fp32", "bf16", "int8")
CARRY_OFFLOADS = ("none", "host")
SYNC_MODES = ("2hop", "allreduce_slice")
HOP1_WIRE_DTYPES = ("fp32", "bf16", "int8")
HOP2_WIRE_DTYPES = ("fp32", "bf16", "int8")
GRAD_ROUNDINGS = ("stochastic", "nearest")

_WIRE_JNP = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class GatherPolicy:
    """How a flat-param pool is all-gathered across its partition group.

    Under ``prefetch=True`` a training step keeps no gathered buffer for
    the backward pass: the backward re-issues each layer's gather
    (models/lm.py ``pool_route``, O(layers x shard) HBM).
    ``carry_offload='host'`` instead keeps the stored carry's schedule (no
    backward re-gather) and streams each layer's gathered buffer to host
    memory in the forward and back to device in the backward
    (core/hostoffload.py) — O(layers x shard) HBM too, priced as the link
    model's host tier instead of an extra all-gather.
    """

    topology: str = "inner_first"  # 'flat' | 'inner_first' | 'outer_first'
    wire_dtype: str = "bf16"       # 'fp32' | 'bf16' | 'int8' (ZeRO++ qwZ)
    inner: int | None = None       # intra-"node" factor for staged gathers
    prefetch: bool = True          # one-slot lookahead layer scan
    carry_offload: str = "none"    # 'none' | 'host' (d2h/h2d carry stream)

    def __post_init__(self):
        if self.topology not in GATHER_TOPOLOGIES:
            raise ValueError(f"unknown gather topology {self.topology!r}")
        if self.wire_dtype not in WIRE_DTYPES:
            raise ValueError(f"unknown wire dtype {self.wire_dtype!r}")
        if self.carry_offload not in CARRY_OFFLOADS:
            raise ValueError(
                f"unknown carry_offload {self.carry_offload!r} "
                f"(expected one of {CARRY_OFFLOADS})")
        if self.carry_offload == "host" and not self.prefetch:
            raise ValueError(
                "carry_offload='host' requires prefetch=True (it offloads "
                "the prefetch schedule's carried buffer)")


@dataclasses.dataclass(frozen=True)
class SyncPolicy:
    """How gradients synchronize (paper §3.4).

    ``hop1_wire_dtype`` is what the per-micro-step adjoint reduce-scatter
    ships: ``'fp32'`` keeps today's behavior (the staged reduce-scatter runs
    in the gather's natural cotangent dtype — bitwise identical to the
    pre-qgZ tree), ``'bf16'`` casts the cotangent before the float staged
    reduce-scatter, ``'int8'`` is the ZeRO++-qgZ analogue — a per-stage
    block-quantized reduce-scatter (int8 + f32 block scales per hop, fp32
    accumulation between hops, ``collectives.quantized_reduce_scatter``).
    ``hop2_wire_dtype='int8'`` is the matching boundary leg (quantized
    reduce-scatter + all-gather, ``collectives.quantized_all_reduce``).
    ``grad_rounding`` picks the int8 gradient quantizer's rounding:
    ``'stochastic'`` (unbiased in expectation, the default) or ``'nearest'``.
    """

    mode: str = "2hop"             # '2hop' | 'allreduce_slice' (Fig 14)
    hop2_wire_dtype: str = "fp32"  # 'fp32' | 'bf16' | 'int8' hop-2 wire
    hop1_wire_dtype: str = "fp32"  # 'fp32' | 'bf16' | 'int8' (ZeRO++ qgZ)
    grad_rounding: str = "stochastic"  # int8 gradient-quantizer rounding

    def __post_init__(self):
        if self.mode not in SYNC_MODES:
            raise ValueError(f"unknown sync mode {self.mode!r}")
        if self.hop2_wire_dtype not in HOP2_WIRE_DTYPES:
            raise ValueError(f"unknown hop-2 wire dtype {self.hop2_wire_dtype!r}")
        if self.hop1_wire_dtype not in HOP1_WIRE_DTYPES:
            raise ValueError(f"unknown hop-1 wire dtype {self.hop1_wire_dtype!r}")
        if self.grad_rounding not in GRAD_ROUNDINGS:
            raise ValueError(f"unknown grad rounding {self.grad_rounding!r}")
        if self.hop1_wire_dtype != "fp32" and self.mode != "2hop":
            raise ValueError(
                "hop-1 wire compression requires the 2hop schedule (the "
                "allreduce_slice ablation has no staged hop-1 to compress)")

    @property
    def stochastic(self) -> bool:
        return self.grad_rounding == "stochastic"


def policies_from_config(mcfg) -> tuple[GatherPolicy, SyncPolicy]:
    """Interpret a ``MiCSConfig``'s legacy flags as (GatherPolicy,
    SyncPolicy) — topology-free, so the memory planner and partition-group
    auto-sizing can price policies before any mesh exists.  The one place
    those flags are interpreted (``CommEngine.from_config`` calls this)."""
    topology = mcfg.gather_order if mcfg.hierarchical else "flat"
    compute = jnp.dtype(mcfg.gather_dtype)
    if mcfg.quant_gather:
        wire = "int8"
    else:
        wire = "bf16" if compute == jnp.dtype(jnp.bfloat16) else "fp32"
    gp = GatherPolicy(
        topology=topology,
        wire_dtype=wire,
        inner=mcfg.hierarchy_inner,
        prefetch=getattr(mcfg, "prefetch", True),
        carry_offload=getattr(mcfg, "carry_offload", "none"),
    )
    hop2 = mcfg.compress_hop2  # bool (legacy) or wire-dtype string
    if hop2 is True:
        hop2 = "bf16"
    elif not hop2:
        hop2 = "fp32"
    sp = SyncPolicy(
        mode=mcfg.sync_mode,
        hop2_wire_dtype=hop2,
        hop1_wire_dtype=getattr(mcfg, "hop1_wire_dtype", "fp32"),
        grad_rounding=getattr(mcfg, "grad_rounding", "stochastic"),
    )
    return gp, sp


class CommEngine:
    """Owns every parameter-gather and gradient-sync collective of one run.

    One engine per (topology, policy) pair; construction is cheap and the
    engine is closed over by jitted step functions (all members are static).
    """

    def __init__(
        self,
        topo: MiCSTopology,
        gather_policy: GatherPolicy = GatherPolicy(),
        sync_policy: SyncPolicy = SyncPolicy(),
        *,
        compute_dtype: Any = jnp.bfloat16,
        model_axis: str = MODEL_AXIS,
    ):
        self.topo = topo
        self.gather_policy = gather_policy
        self.sync_policy = sync_policy
        self.compute_dtype = compute_dtype
        self.model_axis = model_axis
        self._model_gather_fn = model_gather_fn_for(model_axis, topo.model_size)
        self._gather_vjp = self._build_gather_vjp(quantized=False)
        self._quant_gather_vjp = self._build_gather_vjp(quantized=True)
        self._gather_vjp_seeded = self._build_gather_vjp(
            quantized=False, seeded=True)
        self._quant_gather_vjp_seeded = self._build_gather_vjp(
            quantized=True, seeded=True)
        self._host_stash = None     # lazy (hostoffload.HostStash)
        self._carry_tags: dict = {}  # pool name -> stash tag

    # -- construction -------------------------------------------------------
    @classmethod
    def from_config(cls, topo: MiCSTopology, mcfg) -> "CommEngine":
        """Map a ``MiCSConfig`` onto gather/sync policies (the one place the
        legacy flags are interpreted)."""
        gp, sp = policies_from_config(mcfg)
        return cls(topo, gp, sp, compute_dtype=mcfg.gather_dtype)

    # -- properties ---------------------------------------------------------
    @property
    def prefetch(self) -> bool:
        return self.gather_policy.prefetch

    @property
    def carry_offload(self) -> str:
        return self.gather_policy.carry_offload

    @property
    def partition_size(self) -> int:
        return self.topo.partition_size

    @property
    def host_stash(self):
        """Lazy host-memory stash bound to this topology's mesh — the
        d2h/h2d stream backing ``carry_offload='host'`` and the offloaded
        optimizer moments (core/hostoffload.py)."""
        if self._host_stash is None:
            from repro.core.hostoffload import HostStash

            self._host_stash = HostStash(
                tuple(zip(self.topo.mesh.axis_names,
                          self.topo.mesh.devices.shape)))
        return self._host_stash

    def carry_tag(self, pool_name: str) -> int:
        """Stable per-engine stash tag for a pool's offloaded carry."""
        from repro.core.hostoffload import TAG_CARRY_BASE

        if pool_name not in self._carry_tags:
            self._carry_tags[pool_name] = TAG_CARRY_BASE + len(self._carry_tags)
        return self._carry_tags[pool_name]

    def gather_out_dtype(self):
        """Dtype of :meth:`gather_flat`'s full buffer (the wire dtype for
        float wires, the compute dtype for the int8 wire)."""
        gp = self.gather_policy
        if gp.wire_dtype == "int8":
            return jnp.dtype(self.compute_dtype)
        return jnp.dtype(_WIRE_JNP[gp.wire_dtype])

    def describe(self) -> dict:
        """Static policy record (dry-run artifacts, BENCH json)."""
        outer, inner = hierarchy_factors(self.topo, self.gather_policy.inner) \
            if self.topo.partition_size > 1 else (1, 1)
        return {
            "gather": dataclasses.asdict(self.gather_policy),
            "sync": dataclasses.asdict(self.sync_policy),
            "compute_dtype": jnp.dtype(self.compute_dtype).name,
            "partition_axes": list(self.topo.partition_axes),
            "replication_axes": list(self.topo.replication_axes),
            "partition_size": self.topo.partition_size,
            "replication_degree": self.topo.replication_degree,
            "hierarchy": {"outer": outer, "inner": inner},
        }

    # -- raw policy collectives (no VJP override) ---------------------------
    def _policy_all_gather(self, x: jax.Array) -> jax.Array:
        gp = self.gather_policy
        if self.topo.partition_size == 1:
            return x
        if gp.topology == "flat":
            return C.flat_all_gather(x, self.topo.partition_axes)
        return C.hierarchical_all_gather(
            x, self.topo, order=gp.topology, inner=gp.inner)

    def _policy_reduce_scatter(self, g: jax.Array) -> jax.Array:
        gp = self.gather_policy
        if self.topo.partition_size == 1:
            # Nothing to scatter, but keep the flat cotangent a buffer of its
            # own: without the barrier XLA folds unflatten's transpose and
            # the fp32 accumulate into one [rows, cols] -> [1, 1, n]
            # relayout, which the TPU backend emits unrolled per row (minutes
            # of compile for a vocab-sized table).
            return jax.lax.optimization_barrier(g)
        if gp.topology == "flat":
            return C.hop1_reduce_scatter(g, self.topo)
        return C.hierarchical_reduce_scatter(
            g, self.topo, order=gp.topology, inner=gp.inner)

    # -- centralized custom-VJP gathers -------------------------------------
    @jax.named_scope(scopes.HOP1)
    def _adjoint(self, ct: jax.Array, seed=None) -> jax.Array:
        """Hop-1 of §3.4 — or the Fig-14 alternative schedule's full
        all-reduce + slice when the ablation is selected.

        The wire is picked by ``SyncPolicy.hop1_wire_dtype``: ``fp32`` runs
        the staged reduce-scatter in the cotangent's own dtype (bitwise
        today's behavior), ``bf16`` narrows the cotangent first, ``int8``
        runs the qgZ per-stage block-quantized reduce-scatter (int8 + f32
        scales per hop, fp32 accumulation between hops) mirroring the
        gather topology.  The return dtype always matches the cotangent, so
        every gather policy composes with every hop-1 wire.  ``seed`` is the
        step-varying dither seed for the int8 wire's stochastic rounding
        (threaded from the train step; the float wires ignore it).
        """
        if self.sync_policy.mode == "allreduce_slice":
            return C.alternative_sync(ct, self.topo)
        hop1 = self.sync_policy.hop1_wire_dtype
        if hop1 == "int8" and self.topo.partition_size > 1:
            gp = self.gather_policy
            out = C.quantized_reduce_scatter(
                ct, self.topo, topology=gp.topology, inner=gp.inner,
                stochastic=self.sync_policy.stochastic, seed=seed)
            return out.astype(ct.dtype)
        if hop1 == "bf16":
            return self._policy_reduce_scatter(
                ct.astype(jnp.bfloat16)).astype(ct.dtype)
        return self._policy_reduce_scatter(ct)

    def _build_gather_vjp(self, *, quantized: bool, seeded: bool = False):
        """One parameterized builder for both wire families.

        ``quantized=False``: the float wire — gather the row as-is (callers
        cast to the wire dtype).  ``quantized=True``: the int8 blockwise
        wire (ZeRO++ qwZ) — quantize the local fp32 shard to (int8 q, f32
        block scales), all-gather both with the policy topology, dequantize
        to the compute dtype.  Either way the backward is straight-through:
        :meth:`_adjoint` of the (float) cotangent — the exact staged
        reduce-scatter, or its bf16/int8-wire variant when ``SyncPolicy``
        compresses hop 1; the forward quantizer is never differentiated.

        ``seeded=True`` builds the ``gather(row, seed)`` variant: ``seed``
        is a traced int32 scalar (the training step counter) carried as a
        VJP residual into the adjoint, where the int8 hop-1 wire folds it
        into its stochastic-rounding dither key in place of the payload
        fingerprint — the step-varying, value-independent dither the
        ROADMAP qgZ follow-on asked for.  The seed is inert data (integer
        cotangent is float0); float hop-1 wires ignore it entirely.
        """

        def fwd_gather(row):
            if not quantized:
                return self._policy_all_gather(row)
            q, s = Q.quantize_flat(row)
            qg = self._policy_all_gather(q)
            sg = self._policy_all_gather(s)
            return Q.dequantize_flat(qg, sg, dtype=self.compute_dtype)

        if seeded:

            @jax.custom_vjp
            def gather(row, seed):
                return fwd_gather(row)

            def fwd(row, seed):
                return fwd_gather(row), seed

            def bwd(seed, ct):
                if quantized:
                    ct = ct.astype(jnp.float32)
                ct_seed = np.zeros(jnp.shape(seed), jax.dtypes.float0)
                return self._adjoint(ct, seed=seed), ct_seed

            gather.defvjp(fwd, bwd)
            return gather

        @jax.custom_vjp
        def gather(row):
            return fwd_gather(row)

        def fwd(row):
            return fwd_gather(row), None

        def bwd(_, ct):
            if quantized:
                ct = ct.astype(jnp.float32)
            return (self._adjoint(ct),)

        gather.defvjp(fwd, bwd)
        return gather

    # -- public gather API --------------------------------------------------
    @jax.named_scope(scopes.GATHER)
    def gather_flat(self, row, *, seed=None) -> jax.Array:
        """Gather one layer's flat shard into the full flat buffer.

        ``row`` is either a float shard ``[S_local]`` or a pre-quantized
        serving dict ``{'q': int8, 's': f32}`` (``quant.quantize_state``).
        Float wires return the buffer in the wire dtype (which doubles as
        the compute dtype — ``from_config`` keeps them identical); int8
        and stored-int8 rows dequantize to ``compute_dtype``.  One call per
        layer — the coalesced communication of paper §4 by construction.

        ``seed`` (optional traced int32, the training step counter) rides
        the VJP into the adjoint so the int8 qgZ hop-1 wire draws
        step-varying, value-independent dither; ``None`` keeps the legacy
        payload-fingerprint dither (serving and standalone gathers).
        """
        gp = self.gather_policy
        if isinstance(row, dict):  # stored-int8 serving weights
            qg = self._policy_all_gather(row["q"])
            sg = self._policy_all_gather(row["s"])
            return Q.dequantize_flat(qg, sg, dtype=self.compute_dtype)
        if gp.wire_dtype == "int8":
            if self.topo.partition_size == 1:  # nothing on the wire
                return row.astype(self.compute_dtype)
            if seed is not None:
                return self._quant_gather_vjp_seeded(row, seed)
            return self._quant_gather_vjp(row)
        row = row.astype(_WIRE_JNP[gp.wire_dtype])
        if seed is not None:
            return self._gather_vjp_seeded(row, seed)
        return self._gather_vjp(row)

    def unflatten(self, pool, full: jax.Array) -> dict[str, jax.Array]:
        """Rebuild layer tensors, reassembling model-axis-sharded segments."""
        return pool.layout.unflatten(full, model_gather_fn=self._model_gather_fn)

    def gather(self, pool, row, *, seed=None) -> dict[str, jax.Array]:
        return self.unflatten(pool, self.gather_flat(row, seed=seed))

    def gather_flat_adjoint(self, ct: jax.Array, *, seed=None) -> jax.Array:
        """The standalone hop-1 adjoint of :meth:`gather_flat`: full-buffer
        cotangent in, fp32 shard cotangent out.

        Composes exactly what autodiff of ``gather_flat`` composes —
        the custom-VJP backward (:meth:`_adjoint`, including the bf16/int8
        hop-1 wire variants) plus the transpose of the outer wire-dtype
        cast back to the fp32 row — *without* re-running the gather
        forward.  The host-offload carry's hand-rolled backward
        (models/lm.py) needs precisely this: it already holds the full
        buffer (streamed back from the host), so ``jax.vjp`` of the gather
        would re-issue the all-gather for nothing.
        """
        gp = self.gather_policy
        if gp.wire_dtype == "int8":
            if self.topo.partition_size == 1:   # forward was a pure cast
                return ct.astype(jnp.float32)
            return self._adjoint(ct.astype(jnp.float32), seed=seed)
        return self._adjoint(ct, seed=seed).astype(jnp.float32)

    # -- gradient synchronization ------------------------------------------
    @jax.named_scope(scopes.HOP1)
    def hop1_reduce_scatter(self, g: jax.Array) -> jax.Array:
        """Explicit hop-1 (tests / alternative schedules); normally this
        arises as the VJP of :meth:`gather_flat`."""
        return self._policy_reduce_scatter(g)

    @jax.named_scope(scopes.HOP2)
    def hop2(self, g: jax.Array, *, salt: int = 0, seed=None) -> jax.Array:
        """Replication-group all-reduce at the gradient-accumulation
        boundary (§3.4 hop 2), with optional bf16 or int8 wire compression.
        A no-op under the alternative schedule (its backward already
        all-reduced globally).

        ``int8`` is the quantized decompress leg: reduce-scatter +
        all-gather, both shipping (int8 q, f32 block scales) with an fp32
        accumulation in between (``collectives.quantized_all_reduce``);
        ``salt`` decorrelates the stochastic-rounding dither across payloads
        and ``seed`` (the traced step counter) across steps — both ignored
        by the float wires.
        """
        if self.sync_policy.mode != "2hop":
            return g
        wire = self.sync_policy.hop2_wire_dtype
        if wire == "int8" and self.topo.replication_degree > 1:
            return C.quantized_all_reduce(
                g, self.topo, salt=salt,
                stochastic=self.sync_policy.stochastic, seed=seed)
        if wire == "bf16":
            g = g.astype(jnp.bfloat16)
        g = C.hop2_all_reduce(g, self.topo)
        return g.astype(jnp.float32)

    def hop2_bucketed(self, bucket: jax.Array, *, salt: int = 0,
                      seed=None) -> jax.Array:
        """Hop 2 at bucket granularity: the identical replication-group
        all-reduce (same axes, same optional wire compression) applied to
        one fixed-byte slice of a pool's flat gradient shard.

        The boundary scheduler (core/schedule.py) issues these one bucket
        ahead of the dependent norm/decompress compute so the collective
        overlaps it.  Because ``psum`` (and the bf16 cast) is elementwise,
        a bucket of the reduced buffer is bitwise equal to the reduction of
        the bucket — which is what makes the bucketed boundary exactly
        equivalent to the serial reference for the fp32/bf16 wires.  The
        int8 wire's quantization blocks follow the *payload*, so its
        schedules agree only to quantization error (core/collectives.py).
        This stays the single construction point for the collective: same
        code path as :meth:`hop2`, just a different payload shape.
        """
        return self.hop2(bucket, salt=salt, seed=seed)

    # -- misc reductions -----------------------------------------------------
    def partition_coord(self):
        """Linearized index of this device within its partition group."""
        return C._partition_coord(self.topo)

    def replica_mean(self, x: jax.Array) -> jax.Array:
        return C.replica_mean(x, self.topo)
