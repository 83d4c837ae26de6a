"""Jitted public wrappers for flash attention in the model's GQA layout.

``flash_attention_gqa`` runs this package's forward-only kernel.
``causal_attention`` is the training path: JAX's splash attention, a Pallas
forward and a Pallas backward under one ``custom_vjp``, over a block-sparse
causal mask, so the ``[tq, tk]`` scores never reach HBM and the blocks above
the diagonal are neither fetched nor computed.  Its residuals are q, k, v,
the output and the row log-sum-exp; the backward recomputes each score
block from them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels.flash_attention.kernel import flash_attention

# the sequence tile of every splash kernel, forward and backward: the
# largest of these that divides the sequence
BLOCKS = (512, 256, 128)


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


def block_sizes(t: int) -> splash.BlockSizes:
    """The splash tiles for sequence ``t`` (a multiple of 128)."""
    blk = next(b for b in BLOCKS if t % b == 0)
    return splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk)


def causal_attention(
    q: jax.Array,   # [b, t, hkv, g, dh]
    k: jax.Array,   # [b, t, hkv, dh]
    v: jax.Array,   # [b, t, hkv, dv]
    *,
    scale: float | None = None,   # of the scores; None -> 1/sqrt(dh)
    interpret: bool = False,
) -> jax.Array:
    """Causal self attention over a whole sequence of ``t`` (a multiple of
    128) tokens: ``[b, t, hkv, g, dv]``, differentiable.

    q is scaled as ``layers.attention`` scales it (in fp32, back to q's
    dtype); q.k and p.v accumulate in fp32, the softmax statistics are
    fp32.  Query head ``kv * g + i`` reads kv head ``kv`` (splash's grouped
    heads); v may be narrower than q and k (MLA).
    """
    b, t, hkv, g, dh = q.shape
    dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    heads = hkv * g
    kernel = splash.make_splash_mha(
        splash.MultiHeadMask([splash.CausalMask((t, t))] * heads),
        block_sizes=block_sizes(t), head_shards=1, q_seq_shards=1,
        interpret=interpret)
    out = jax.vmap(kernel)(
        qs.reshape(b, t, heads, dh).transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3).reshape(b, t, hkv, g, dv)
