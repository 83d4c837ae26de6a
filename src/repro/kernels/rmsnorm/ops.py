"""Jitted public wrapper for the RMSNorm kernel (any leading shape)."""

from __future__ import annotations

import functools

import jax

from repro.kernels.rmsnorm.kernel import rmsnorm


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_nd(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
               interpret: bool = False):
    lead = x.shape[:-1]
    n = 1
    for s in lead:
        n *= s
    block = block_rows
    while n % block:
        block //= 2
    out = rmsnorm(x.reshape(n, x.shape[-1]), scale, eps=eps,
                  block_rows=max(block, 1), interpret=interpret)
    return out.reshape(*lead, x.shape[-1])
