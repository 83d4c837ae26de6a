"""Jitted public wrapper for the RG-LRU kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels.rglru.kernel import rglru


@functools.partial(jax.jit, static_argnames=("block_t", "block_c", "interpret"))
def rglru_scan(a, b, *, block_t: int = 256, block_c: int = 128,
               interpret: bool = False):
    bt, bc = block_t, block_c
    while a.shape[1] % bt:
        bt //= 2
    while a.shape[2] % bc:
        bc //= 2
    return rglru(a, b, block_t=max(bt, 1), block_c=max(bc, 1),
                 interpret=interpret)
