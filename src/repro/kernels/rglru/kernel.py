"""RG-LRU sequence-scan Pallas TPU kernel.

The RG-LRU recurrence h_t = a_t * h_{t-1} + b_t is diagonal per channel, so
the kernel tiles channels across the parallel grid dimension and walks time
chunks sequentially, carrying the running state h in VMEM scratch across the
"arbitrary" time-grid dimension.  Within a time block, a fori_loop reads one
sublane-aligned slab of ``rows`` time steps at a time from the refs, unrolls
the recurrence over the slab's rows and writes the slab back whole — on TPU
this trades the log-depth associative scan (which materializes 2x[T,C]
intermediates in HBM) for a single streaming pass with O(block_c) state.

Inputs are the precomputed per-step coefficients (a, b) — gate math stays in
XLA where it fuses with the surrounding projections; the kernel owns only the
memory-bound sequential part.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows per slab: one packed bf16 sublane tile (16 rows), two f32 tiles.
SLAB_ROWS = 16


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, block_t: int, rows: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def slab(i, h):                        # h: [1, block_c] fp32
        lo = pl.multiple_of(i * rows, rows)
        a = a_ref[0, pl.ds(lo, rows), :].astype(jnp.float32)
        b = b_ref[0, pl.ds(lo, rows), :].astype(jnp.float32)
        out = []
        for r in range(rows):
            h = a[r:r + 1] * h + b[r:r + 1]
            out.append(h)
        o_ref[0, pl.ds(lo, rows), :] = jnp.concatenate(out).astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_t // rows, slab, h_ref[...])


def rglru(
    a: jax.Array,          # [b, T, c] decay coefficients in (0, 1)
    b: jax.Array,          # [b, T, c] input terms
    *,
    block_t: int = 256,
    block_c: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bsz, t, c = a.shape
    block_t = min(block_t, t)
    block_c = min(block_c, c)
    if t % block_t or c % block_c:
        raise ValueError(f"dims ({t},{c}) must divide blocks ({block_t},{block_c})")
    kernel = functools.partial(_rglru_kernel, block_t=block_t,
                               rows=math.gcd(block_t, SLAB_ROWS))
    return pl.pallas_call(
        kernel,
        grid=(bsz, c // block_c, t // block_t),
        in_specs=[
            pl.BlockSpec((1, block_t, block_c), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, block_t, block_c), lambda i, j, k: (i, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_c), lambda i, j, k: (i, k, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, t, c), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
