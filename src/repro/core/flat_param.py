"""Flat parameter pools: ZeRO-3 / MiCS uniform model-state partitioning.

DeepSpeed (and therefore MiCS) shards each layer's parameters as one flat,
contiguous, uniformly divided buffer — which is also what makes the paper's
"coalesced communication" and "memory defragmentation" optimizations natural.
We reproduce that layout directly:

* every block's TP-local tensors are flattened and concatenated into one
  fp32 vector, padded so any partition-group size divides it;
* the vector (plus Adam's m/v, same shape) is what MiCS shards over the
  partition group — gathering a layer is ONE collective (coalesced by
  construction, paper §4), and XLA's static allocation of the pool is the
  analogue of the paper's preallocated contiguous buffers;
* segment metadata records how to rebuild tensors, which elements receive
  weight decay, and which segments must be re-assembled across the tensor-
  parallel axis at use time (norm scales, d_model biases, grouped-KV
  projections) — those are stored model-sharded and all-gathered over
  'model' sub-groups on use, so **no parameter is ever stored replicated**
  and no gradient fix-ups are needed: every collective's adjoint is exact.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
from jax import lax

# Any partition-group size we ever use (<= 32 data-parallel participants in
# ZeRO-3 multi-pod mode) times the 128-lane TPU alignment.
PAD_MULTIPLE = 32 * 128


@dataclasses.dataclass(frozen=True)
class Segment:
    """One logical tensor inside a flat pool (shapes are TP-local)."""

    name: str
    shape: tuple[int, ...]
    offset: int            # element offset into the flat vector
    decay: bool            # weight decay applies to this segment
    init: str              # 'normal' | 'zeros' | 'ones'
    std: float             # stddev for 'normal'
    model_gather: int = 1  # all-gather group size over the model axis at use
    model_gather_dim: int = 0

    @property
    def size(self) -> int:
        return math.prod(self.shape)

    @property
    def end(self) -> int:
        return self.offset + self.size


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static description of a flat pool; shared by every layer in a stack."""

    segments: tuple[Segment, ...]
    raw_len: int
    flat_len: int

    # -- construction -------------------------------------------------------
    @staticmethod
    def build(segments: Iterable[Segment]) -> "FlatLayout":
        segs = tuple(segments)
        raw = segs[-1].end if segs else 0
        flat = ((raw + PAD_MULTIPLE - 1) // PAD_MULTIPLE) * PAD_MULTIPLE
        flat = max(flat, PAD_MULTIPLE)
        return FlatLayout(segs, raw, flat)

    def seg(self, name: str) -> Segment:
        for s in self.segments:
            if s.name == name:
                return s
        raise KeyError(name)

    @property
    def param_count(self) -> int:
        return self.raw_len

    # -- tensor <-> flat -----------------------------------------------------
    def unflatten(
        self,
        flat: jax.Array,
        *,
        model_gather_fn: Callable[[Segment, jax.Array], jax.Array] | None = None,
    ) -> dict[str, jax.Array]:
        """Rebuild tensors from a gathered flat vector.

        ``model_gather_fn`` reassembles model-axis-sharded segments (identity
        outside shard_map / at tp=1).
        """
        out = {}
        for s in self.segments:
            t = lax.slice_in_dim(flat, s.offset, s.end, axis=0).reshape(s.shape)
            if s.model_gather > 1 and model_gather_fn is not None:
                t = model_gather_fn(s, t)
            out[s.name] = t
        return out

    def flatten(self, tensors: Mapping[str, jax.Array], dtype=jnp.float32) -> jax.Array:
        parts = []
        cursor = 0
        for s in self.segments:
            if s.offset != cursor:
                raise ValueError("segments are not contiguous")
            parts.append(tensors[s.name].reshape(-1).astype(dtype))
            cursor = s.end
        pad = self.flat_len - self.raw_len
        if pad:
            parts.append(jnp.zeros((pad,), dtype))
        return jnp.concatenate(parts) if parts else jnp.zeros((self.flat_len,), dtype)

    # -- init ----------------------------------------------------------------
    def init_flat(self, key: jax.Array, dtype=jnp.float32) -> jax.Array:
        """Full flat vector init (used under jit with sharded out_shardings).

        Each segment is drawn already flat: JAX's random values do not
        depend on the shape they are drawn in, and a [rows, cols] -> flat
        reshape here costs the TPU compiler minutes for a vocab-sized
        table (it emits the relayout unrolled per row)."""
        tensors = {}
        for s in self.segments:
            key, sub = jax.random.split(key)
            shape = (s.size,)
            if s.init == "normal":
                t = jax.random.normal(sub, shape, dtype) * jnp.asarray(s.std, dtype)
            elif s.init == "zeros":
                t = jnp.zeros(shape, dtype)
            elif s.init == "ones":
                t = jnp.ones(shape, dtype)
            elif s.init == "lru":
                # RG-LRU Λ such that the per-channel decay a = sigmoid(Λ) is
                # uniform in [0.9, 0.999] (Griffin appendix initialization).
                u = jax.random.uniform(sub, shape, dtype, 0.9, 0.999)
                t = jnp.log(u) - jnp.log1p(-u)
            else:
                raise ValueError(f"unknown init {s.init!r}")
            tensors[s.name] = t
        return self.flatten(tensors, dtype)

    # -- masks ----------------------------------------------------------------
    def nodecay_ranges(self) -> list[tuple[int, int]]:
        rng = [(s.offset, s.end) for s in self.segments if not s.decay]
        rng.append((self.raw_len, self.flat_len))  # padding never decays
        return rng

    def decay_mask_for_shard(self, shard_start, shard_len: int) -> jax.Array:
        """Decay mask for the local shard [shard_start, shard_start+shard_len).

        Built from static ranges + dynamic shard offset so no device ever
        materializes the full-length mask.
        """
        gidx = shard_start + jnp.arange(shard_len, dtype=jnp.int32)
        mask = jnp.ones((shard_len,), jnp.float32)
        for lo, hi in self.nodecay_ranges():
            if lo >= hi:
                continue
            inside = (gidx >= lo) & (gidx < hi)
            mask = jnp.where(inside, 0.0, mask)
        return mask

    def padding_mask_for_shard(self, shard_start, shard_len: int) -> jax.Array:
        """1.0 for real parameters, 0.0 for the padded tail."""
        gidx = shard_start + jnp.arange(shard_len, dtype=jnp.int32)
        return (gidx < self.raw_len).astype(jnp.float32)


# ---------------------------------------------------------------------------
# fixed-byte bucketization (the boundary scheduler's unit of pipelining)
# ---------------------------------------------------------------------------

def bucket_elems(bucket_mb: float, itemsize: int = 4) -> int:
    """Elements per fixed-byte bucket (>= 1 even for degenerate sizes)."""
    if bucket_mb <= 0:
        raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
    return max(1, int(bucket_mb * 1e6) // itemsize)


def partition_buckets(
    n_elems: int, bucket_mb: float, itemsize: int = 4
) -> tuple[tuple[int, int], ...]:
    """Split ``[0, n_elems)`` into contiguous ``(lo, hi)`` buckets of at most
    ``bucket_mb`` megabytes each (``itemsize`` bytes per element).

    Static Python ints — the boundary scheduler (core/schedule.py) unrolls
    over these, so bucket count is a compile-time property.  Degenerate
    cases: ``bucket_mb`` larger than the whole buffer yields one bucket;
    every element is covered exactly once in order.
    """
    if n_elems <= 0:
        return ()
    per = bucket_elems(bucket_mb, itemsize)
    return tuple(
        (lo, min(lo + per, n_elems)) for lo in range(0, n_elems, per)
    )


class LayoutBuilder:
    """Accumulates segments with automatic offsets."""

    def __init__(self, prefix: str = ""):
        self.prefix = prefix
        self._segments: list[Segment] = []
        self._cursor = 0

    def add(
        self,
        name: str,
        shape: tuple[int, ...],
        *,
        decay: bool = True,
        init: str = "normal",
        std: float | None = None,
        model_gather: int = 1,
        model_gather_dim: int = 0,
    ) -> None:
        if std is None:
            fan_in = shape[0] if len(shape) > 1 else max(shape[-1], 1)
            std = 1.0 / math.sqrt(max(fan_in, 1))
        seg = Segment(
            name=self.prefix + name,
            shape=tuple(int(d) for d in shape),
            offset=self._cursor,
            decay=decay,
            init=init,
            std=float(std),
            model_gather=int(model_gather),
            model_gather_dim=int(model_gather_dim),
        )
        self._segments.append(seg)
        self._cursor += seg.size

    def extend(self, other: "LayoutBuilder") -> None:
        """Inline another builder's segments (namespaced) after ours."""
        for s in other._segments:
            self._segments.append(dataclasses.replace(s, offset=self._cursor))
            self._cursor += s.size

    def build(self) -> FlatLayout:
        return FlatLayout.build(self._segments)


# ---------------------------------------------------------------------------
# model-axis gathering of sharded small segments
# ---------------------------------------------------------------------------

def model_gather_fn_for(axis_name: str, axis_size: int):
    """Returns the gather fn used inside shard_map to reassemble segments that
    are stored sharded over the model axis (norm scales, grouped-KV
    projections).  Group size g < axis_size gathers over contiguous sub-groups
    (ranks sharing the same KV head); g == axis_size gathers fully.
    The adjoint (psum_scatter over the same groups) is exact, so these
    parameters need no gradient fix-up.
    """

    def fn(seg: Segment, t: jax.Array) -> jax.Array:
        g = seg.model_gather
        if g <= 1 or axis_size == 1:
            return t
        if g == axis_size:
            return lax.all_gather(t, axis_name, axis=seg.model_gather_dim, tiled=True)
        groups = [list(range(i * g, (i + 1) * g)) for i in range(axis_size // g)]
        return lax.all_gather(
            t, axis_name, axis=seg.model_gather_dim, tiled=True,
            axis_index_groups=groups,
        )

    return fn


def identity_gather_fn(seg: Segment, t: jax.Array) -> jax.Array:
    return t
