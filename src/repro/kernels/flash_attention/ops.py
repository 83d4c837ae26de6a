"""Jitted public wrapper for the flash-attention kernel (GQA-aware)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret",
                                             "block_q", "block_k"))
def flash_attention_gqa(
    q: jax.Array,   # [b, tq, hkv, g, dh]  (layout used by models/layers.py)
    k: jax.Array,   # [b, tk, hkv, dh]
    v: jax.Array,   # [b, tk, hkv, dh]
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, tq, hkv, g, dh = q.shape
    tk = k.shape[1]
    qf = jnp.moveaxis(q, 1, 3).reshape(b * hkv * g, tq, dh)
    kf = jnp.repeat(jnp.moveaxis(k, 1, 2), g, axis=1).reshape(b * hkv * g, tk, dh)
    vf = jnp.repeat(jnp.moveaxis(v, 1, 2), g, axis=1).reshape(b * hkv * g, tk, dh)
    out = flash_attention(qf, kf, vf, causal=causal, window=window,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return jnp.moveaxis(out.reshape(b, hkv, g, tq, dh), 3, 1)
