"""Mesh topology for MiCS: partition groups and replication groups as mesh axes.

The paper divides ``n`` devices into *partition groups* of size ``p`` (each
holding one complete, internally partitioned replica of the model states) and
*replication groups* (same-local-rank devices across partition groups, holding
identical shards).  On TPU we realize this by factoring the ``data`` axis of
the production mesh ``(pod, data, model)`` into ``(repl, shard)`` with
``shard == p``:

    all-gather over 'shard'            = intra-partition-group gather
    psum_scatter over 'shard'          = hop-1 gradient reduce-scatter
    psum over ('pod', 'repl')          = hop-2 replication-group all-reduce

ZeRO-3 is the degenerate case ``partition_axes == all data-like axes`` with
no replication axes; the same code path covers both (§3.2 of the paper).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.linkmodel import V5E

# Mesh axis names, fixed across the framework.
POD_AXIS = "pod"
REPL_AXIS = "repl"
SHARD_AXIS = "shard"
DP2_AXIS = "dp2"     # leftover of the model axis donated to data parallelism
MODEL_AXIS = "model"

MICS_AXES = (POD_AXIS, REPL_AXIS, SHARD_AXIS, DP2_AXIS, MODEL_AXIS)

# v5e-class hardware constants (roofline + partition-size heuristic).
# The single source of truth is the link-profile table
# (core/linkmodel.py); these aliases keep the historical names alive for
# the heuristics below and tests.
HBM_BYTES_PER_CHIP = V5E.hbm_bytes
PEAK_BF16_FLOPS = V5E.peak_flops
HBM_BW = V5E.hbm_bw
ICI_BW_PER_LINK = V5E.intra.bandwidth
# DCI (inter-pod) modeled as a scarce slow link per pod boundary.
DCI_BW_PER_LINK = V5E.inter.bandwidth

# Adam mixed precision footprint: fp32 master + fp32 m + fp32 v + fp32 grad
# accumulator (the transient bf16 gathered copy is per-layer, not persistent).
MODEL_STATE_BYTES_PER_PARAM = 16


def _auto(n: int):
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The assignment's production mesh: 16x16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD_AXIS, "data", MODEL_AXIS) if multi_pod else ("data", MODEL_AXIS)
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mics_mesh(base: Mesh, partition_size: int, tp: int | None = None) -> Mesh:
    """Refactor the production mesh into the MiCS mesh.

    ``data`` index ``d`` maps to ``(repl, shard) = divmod(d, partition_size)``
    so a partition group is a contiguous run of data-axis neighbours (fast
    ICI ring segment) — the paper's "consecutive ranks form a partition
    group".  Optionally the ``model`` axis is factored into ``(dp2, tp)``:
    architectures too small to exploit 16-way tensor parallelism donate the
    leftover factor to data parallelism (beyond-paper optimization,
    EXPERIMENTS.md §Perf).
    """
    names = base.axis_names
    devices = base.devices  # ndarray shaped like base
    if POD_AXIS in names:
        pods, data, model = devices.shape
    else:
        pods = 1
        data, model = devices.shape
        devices = devices.reshape(pods, data, model)
    if data % partition_size != 0:
        raise ValueError(
            f"partition size {partition_size} does not divide data axis {data}"
        )
    tp = model if tp is None else tp
    if model % tp != 0:
        raise ValueError(f"tp {tp} does not divide model axis {model}")
    repl = data // partition_size
    devs = devices.reshape(pods, repl, partition_size, model // tp, tp)
    return Mesh(devs, MICS_AXES, axis_types=_auto(5))


def make_host_mesh(
    pods: int = 1, repl: int = 1, shard: int = 1, model: int = 1, dp2: int = 1
) -> Mesh:
    """Small mesh over however many (virtual) devices exist — for tests."""
    n = pods * repl * shard * dp2 * model
    devs = np.array(jax.devices()[:n]).reshape(pods, repl, shard, dp2, model)
    return Mesh(devs, MICS_AXES, axis_types=_auto(5))


def elastic_host_topology(n_devices: int, partition_size: int,
                          tp: int = 1) -> MiCSTopology:
    """MiCSTopology over the first ``n_devices`` surviving (virtual) devices.

    The elastic train loop's mesh half (the policy half is
    ``autotune.resolve_world``): after a world change the survivors are
    re-factored as ``(pod=1, repl=n/(p·tp), shard=p, dp2=1, model=tp)`` —
    partition groups stay contiguous runs (the paper's consecutive-rank
    rule), the TP degree is pinned (flat layouts are TP-local, the
    checkpointer's one resharding invariant), and everything else reshards
    freely on restore.
    """
    if n_devices <= 0:
        raise ValueError(f"need at least one device, got {n_devices}")
    if n_devices % (partition_size * tp):
        raise ValueError(
            f"world of {n_devices} devices does not factor as "
            f"partition_size={partition_size} x tp={tp}")
    if n_devices > len(jax.devices()):
        raise ValueError(
            f"world of {n_devices} devices exceeds the {len(jax.devices())} "
            f"available")
    repl = n_devices // (partition_size * tp)
    return MiCSTopology(make_host_mesh(1, repl, partition_size, tp))


@dataclasses.dataclass(frozen=True)
class MiCSTopology:
    """Static description of how model states map onto a MiCS mesh.

    partition_axes: mesh axes whose product is the partition group (the ``p``
      devices jointly holding one model-state replica).  Ordered slowest
      link first — hierarchical gathers stage over them in order.
    replication_axes: mesh axes across which shards are replicated (hop-2
      all-reduce runs over these at the gradient-accumulation boundary).
    """

    mesh: Mesh
    partition_axes: tuple[str, ...] = (SHARD_AXIS,)
    replication_axes: tuple[str, ...] = (POD_AXIS, REPL_AXIS, DP2_AXIS)

    def __post_init__(self):
        names = set(self.mesh.axis_names)
        for ax in self.partition_axes + self.replication_axes:
            if ax not in names:
                raise ValueError(f"axis {ax!r} not in mesh {self.mesh.axis_names}")
        overlap = set(self.partition_axes) & set(self.replication_axes)
        if overlap:
            raise ValueError(f"axes {overlap} both partition and replication")

    # -- sizes ------------------------------------------------------------
    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name]

    @property
    def partition_size(self) -> int:  # p
        return math.prod(self.axis_size(a) for a in self.partition_axes)

    @property
    def replication_degree(self) -> int:  # n / p
        return math.prod(self.axis_size(a) for a in self.replication_axes)

    @property
    def model_size(self) -> int:
        return self.axis_size(MODEL_AXIS) if MODEL_AXIS in self.mesh.axis_names else 1

    @property
    def data_axes(self) -> tuple[str, ...]:
        """All axes that carry data parallelism (batch is sharded over these)."""
        return tuple(
            a for a in self.mesh.axis_names if a != MODEL_AXIS
        )

    @property
    def data_parallel_size(self) -> int:
        return math.prod(self.axis_size(a) for a in self.data_axes)

    @property
    def world_size(self) -> int:
        return self.mesh.size

    # -- shardings ---------------------------------------------------------
    def flat_param_sharding(self) -> NamedSharding:
        """[L, shard_len] flat pool: sharded over partition axes only."""
        return NamedSharding(self.mesh, P(None, self.partition_axes))

    def scalar_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self, extra_dims: int = 1) -> NamedSharding:
        """Batch dim sharded over every data axis; trailing dims replicated."""
        return NamedSharding(self.mesh, P(self.data_axes, *([None] * extra_dims)))

    def batch_spec(self, extra_dims: int = 1) -> P:
        return P(self.data_axes, *([None] * extra_dims))

    def flat_param_spec(self) -> P:
        return P(None, self.partition_axes)

    # -- group tables (for diagnostics / axis_index_groups) ----------------
    def partition_groups(self) -> list[list[int]]:
        """Global device ids of each partition group (paper Fig 3)."""
        mesh_devs = self.mesh.devices
        ids = np.vectorize(lambda d: d.id)(mesh_devs)
        # Move partition axes last, flatten the rest.
        names = list(self.mesh.axis_names)
        part_idx = [names.index(a) for a in self.partition_axes]
        other_idx = [i for i in range(len(names)) if i not in part_idx]
        perm = other_idx + part_idx
        arr = np.transpose(ids, perm).reshape(-1, self.partition_size)
        return [list(map(int, row)) for row in arr]

    def replication_groups(self) -> list[list[int]]:
        """Devices holding the same shard (paper's replication groups)."""
        mesh_devs = self.mesh.devices
        ids = np.vectorize(lambda d: d.id)(mesh_devs)
        names = list(self.mesh.axis_names)
        repl_idx = [names.index(a) for a in self.replication_axes]
        other_idx = [i for i in range(len(names)) if i not in repl_idx]
        perm = other_idx + repl_idx
        arr = np.transpose(ids, perm).reshape(-1, self.replication_degree)
        return [list(map(int, row)) for row in arr]


def choose_partition_size(
    param_count: int,
    *,
    data_axis: int = 16,
    model_axis: int = 16,
    hbm_bytes: int = HBM_BYTES_PER_CHIP,
    state_bytes_per_param: int = MODEL_STATE_BYTES_PER_PARAM,
    reserve_fraction: float = 0.35,
) -> int:
    """Paper §5.1.1 heuristic: the smallest partition group that fits.

    Model states are already divided by the tensor-parallel degree; the
    partition group then divides the remainder.  ``reserve_fraction`` of HBM
    is left for activations, KV caches and collective staging buffers.
    """
    budget = hbm_bytes * (1.0 - reserve_fraction)
    per_device_full = param_count * state_bytes_per_param / model_axis
    p = 1
    while p <= data_axis:
        if per_device_full / p <= budget:
            return p
        p *= 2
    raise ValueError(
        f"model with {param_count/1e9:.1f}B params does not fit even with "
        f"p={data_axis} (needs {per_device_full/data_axis/1e9:.1f} GB/device)"
    )


def default_hierarchy_inner(p: int) -> int:
    """Default intra-"node" factor: the largest power-of-two ≤ sqrt(p) that
    divides p — the 2-D analogue of the paper's (p/k nodes) × (k per node).
    The single source of truth for the staged gather, its adjoint
    reduce-scatter, and ``hierarchy_factors``."""
    inner = 1
    while inner * inner <= p // 2 and p % (inner * 2) == 0:
        inner *= 2
    return inner


def hierarchy_factors(topo: MiCSTopology, inner: int | None = None) -> tuple[int, int]:
    """Factor the partition group as (outer, inner) for hierarchical comm.

    When the partition group spans multiple mesh axes, the factorization is
    the axis split itself (slow axis = outer).  Within a single axis, the
    default inner factor is the largest power-of-two ≤ sqrt(p) — the 2-D
    analogue of the paper's (p/k nodes) × (k per node).
    """
    p = topo.partition_size
    if len(topo.partition_axes) > 1:
        outer = topo.axis_size(topo.partition_axes[0])
        return outer, p // outer
    if inner is None:
        inner = default_hierarchy_inner(p)
    if p % inner != 0:
        raise ValueError(f"inner factor {inner} does not divide p={p}")
    return p // inner, inner
