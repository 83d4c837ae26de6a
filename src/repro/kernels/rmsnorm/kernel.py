"""Fused RMSNorm Pallas TPU kernel.

One HBM round-trip per row block: reads x, computes the fp32 mean-square and
normalized output in VMEM, writes the result.  Row blocks keep the working
set (block_rows x d fp32) inside VMEM; d stays whole because the reduction is
over the feature axis (MXU-free, VPU-friendly).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    scale = 1.0 + s_ref[...].astype(jnp.float32)
    o_ref[...] = (y * scale[None, :]).astype(o_ref.dtype)


def rmsnorm(
    x: jax.Array,          # [n, d]
    scale: jax.Array,      # [d]
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    n, d = x.shape
    block_rows = min(block_rows, n)
    if n % block_rows:
        raise ValueError(f"rows {n} must divide block_rows {block_rows}")
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x, scale)
