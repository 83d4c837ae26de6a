"""Fused flash-attention Pallas TPU kernel (causal + optional local window).

TPU adaptation of the IO-aware attention idea: the [T, S] score matrix never
touches HBM — each (batch*head, q-block) grid cell streams K/V blocks through
VMEM, keeping the online-softmax state (m, l, acc) in VMEM scratch.  Block
shapes are MXU-aligned (multiples of 128 on the contraction dims).

Grid: (bh, nq, nk) with the kv dimension innermost ("arbitrary" semantics so
scratch carries across kv steps).  Fully-masked kv blocks are skipped with
pl.when — for causal+window attention the skipped blocks make the kernel's
effective FLOPs sub-quadratic, matching the chunked pure-jnp oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 block_q: int, block_k: int, seq_k: int, causal: bool,
                 window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * block_q
    k_lo = ki * block_k

    # block-level mask decision (static per grid cell at run time)
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_lo <= q_lo + block_q - 1)
    if window:
        run = jnp.logical_and(run, k_lo + block_k - 1 >= q_lo - window + 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale      # [bq, d]
        k = k_ref[0].astype(jnp.float32)              # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_k
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                           # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,        # [bh, Tq, d]
    k: jax.Array,        # [bh, Tk, d]
    v: jax.Array,        # [bh, Tk, d]
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if tq % block_q or tk % block_k:
        raise ValueError(f"seq lens ({tq},{tk}) must divide blocks "
                         f"({block_q},{block_k})")
    nq, nk = tq // block_q, tk // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, seq_k=tk,
        causal=causal, window=window, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
