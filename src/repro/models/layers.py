"""Numeric building blocks shared by every architecture.

Everything here is a pure function over explicit tensors; tensor-parallel
collectives use named mesh axes (the functions are always called inside
``shard_map`` — on a single device the axes simply have size 1).

:func:`attention` runs a whole causal sequence on the TPU through the fused
Pallas kernel of ``repro.kernels.flash_attention``, and every other call
through chunked jnp scores (:func:`attention_path` says which).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.flash_attention import causal_attention
from repro.kernels.flash_attention.ops import BLOCKS

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# execution context
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Ctx:
    """Static + dynamic context threaded through block applications."""

    mode: str = "train"            # train | prefill | decode
    tp_axis: str = "model"
    tp: int = 1
    pos: Any = None                # decode: current position scalar (traced)
    cache_len: int = 0             # decode: static KV-cache capacity
    window: int = 0                # local-attention window override
    vision: Any = None             # [b, n_img, d] stub patch embeddings
    enc_out: Any = None            # [b, n_frames, d] encoder output
    compute_dtype: Any = jnp.bfloat16
    scores_bf16: bool = False      # bf16 jnp attention scores (§Perf)
    mlstm_chunk: int = 0           # chunkwise-parallel mLSTM (§Perf; 0=scan)
    step_seed: Any = None          # traced step counter (qgZ dither seed)
    pages: Any = None              # paged-KV state (runtime/paged.PageState):
    #                                block_tables [b, max_blocks] + block_size;
    #                                None = contiguous cache (the default)

    @property
    def scores_dtype(self):
        return jnp.bfloat16 if self.scores_bf16 else jnp.float32

    def tp_index(self):
        if self.tp == 1:
            return jnp.int32(0)
        return lax.axis_index(self.tp_axis)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32)) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rotary(x: jax.Array, positions: jax.Array, theta: float,
           inv_freq: jax.Array | None = None) -> jax.Array:
    """x: [b, t, h, dh]; positions: [b, t] absolute token positions.
    ``inv_freq`` [dh/2] replaces the plain ``theta`` frequencies (YaRN)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = inv_freq if inv_freq is not None else jnp.exp(
        -math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [b, t, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor (DeepSeek-V2 ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max_pos: int,
                  beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN's rope frequencies [dim/2] (DeepSeek-V2
    ``DeepseekV2YarnRotaryEmbedding``): the plain frequencies where a
    dimension turns more than ``beta_fast`` times over the original context,
    those divided by ``factor`` where it turns fewer than ``beta_slow``
    times, and a linear ramp between."""
    def corr(rot):
        return (dim * math.log(original_max_pos / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), dim - 1)
    hi = hi + 0.001 if hi == lo else hi
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _gqa_scores(q: jax.Array, k: jax.Array, dtype=jnp.float32) -> jax.Array:
    """q [b, tq, hkv, g, dh] x k [b, tk, hkv, dh] -> [b, hkv, g, tq, tk]."""
    return jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=dtype
    )


def _masked_softmax(s: jax.Array, bias: jax.Array) -> jax.Array:
    """Numerically-stable softmax in the score dtype; the row-max and the
    normalizer are kept in fp32 (flash-kernel-style) so bf16 scores only
    halve the HBM traffic of the [tq, tk] tensors, not the statistics.

    ``bias`` is [tq, tk] (shared across the batch) or [b, tq, tk]
    (per-request masks for continuous batching)."""
    if bias.ndim == 3:
        s = s + bias[:, None, None].astype(s.dtype)
    else:
        s = s + bias[None, None, None].astype(s.dtype)
    m = lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    denom = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
    return p / jnp.maximum(denom, 1e-30).astype(p.dtype)


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p [b, hkv, g, tq, tk] x v [b, tk, hkv, dv] -> [b, tq, hkv, g, dv]."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def _mask_bias(
    q_pos: jax.Array, k_pos: jax.Array, *, causal: bool, window: int,
    kv_valid_len: jax.Array | None = None,
) -> jax.Array:
    """[tq, tk] additive mask (0 allowed, NEG_INF blocked)."""
    m = jnp.zeros((q_pos.shape[0], k_pos.shape[0]), jnp.float32)
    diff = q_pos[:, None] - k_pos[None, :]
    if causal:
        m = jnp.where(diff < 0, NEG_INF, m)
    if window:
        m = jnp.where(diff >= window, NEG_INF, m)
    if kv_valid_len is not None:
        m = jnp.where(k_pos[None, :] >= kv_valid_len, NEG_INF, m)
    return m


# the fused kernel runs sequences that are multiples of its smallest tile
KERNEL_TILE = min(BLOCKS)

# the Counter that count_attention_paths() has open, if any
_PATHS: contextvars.ContextVar = contextvars.ContextVar("attention_paths",
                                                        default=None)


def attention_path(
    tq: int, tk: int, *, causal: bool, window: int, q_offset: Any,
    k_offset: Any, kv_valid_len: Any, platform: str,
) -> str:
    """The path :func:`attention` takes: ``'kernel'`` (the fused causal
    kernel with its own backward) for causal self attention over a whole
    sequence, no window, at a multiple of :data:`KERNEL_TILE`, on the TPU;
    ``'jnp'`` (chunked scores) for everything else: decode against a cache,
    offsets, local windows, cross attention, other backends."""
    def zero(offset):
        return isinstance(offset, int) and offset == 0

    whole = (kv_valid_len is None and zero(q_offset) and zero(k_offset)
             and tq == tk and tq % KERNEL_TILE == 0)
    if platform == "tpu" and causal and not window and whole:
        return "kernel"
    return "jnp"


@contextlib.contextmanager
def count_attention_paths():
    """While open, tally the :func:`attention_path` of each
    :func:`attention` call traced; yields the ``collections.Counter``."""
    counts = collections.Counter()
    token = _PATHS.set(counts)
    try:
        yield counts
    finally:
        _PATHS.reset(token)


def attention(
    q: jax.Array,                 # [b, tq, hkv, g, dh]
    k: jax.Array,                 # [b, tk, hkv, dh]
    v: jax.Array,                 # [b, tk, hkv, dv]
    *,
    scale: float | None = None,   # of the scores; None -> 1/sqrt(dh)
    causal: bool = True,
    window: int = 0,
    q_offset: Any = 0,            # absolute position of q[0]
    k_offset: Any = 0,
    kv_valid_len: Any = None,     # decode: number of valid cache entries
    chunk_q: int = 512,
    scores_dtype=jnp.float32,
) -> jax.Array:
    """Scaled-dot-product GQA attention, chunked over queries.

    Direct path for short query lengths; otherwise a `lax.scan` over query
    chunks (scores are [chunk_q, tk] — memory-bounded for 32k prefill).
    Local-window attention slices the KV to a static-length window per query
    chunk, so HLO FLOPs reflect the sub-quadratic cost.
    scores_dtype=bf16 halves the HBM traffic of the score/probability
    tensors (fp32 statistics retained) — beyond-paper optimization, §Perf.

    Where :func:`attention_path` says ``'kernel'``, none of that: the
    fused kernel takes the call, with the same scale on q, and
    ``scores_dtype`` and ``chunk_q`` do not apply.
    """
    b, tq, hkv, g, dh = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    path = attention_path(tq, tk, causal=causal, window=window,
                          q_offset=q_offset, k_offset=k_offset,
                          kv_valid_len=kv_valid_len,
                          platform=jax.default_backend())
    counts = _PATHS.get()
    if counts is not None:
        counts[path] += 1
    if path == "kernel":
        return causal_attention(q, k, v, scale=scale)
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    # Per-request valid lengths ([b] or [b, tq]) produce a [b, tq, tk] bias;
    # a scalar kv_valid_len keeps the exact legacy [tq, tk] code path.
    batched_valid = kv_valid_len is not None and getattr(kv_valid_len, "ndim", 0) >= 1

    def direct(qc, q_pos):
        bias = _mask_bias(
            q_pos, k_offset + jnp.arange(tk), causal=causal, window=window,
            kv_valid_len=None if batched_valid else kv_valid_len,
        )
        if batched_valid:
            kvl = kv_valid_len if kv_valid_len.ndim == 2 else kv_valid_len[:, None]
            invalid = (k_offset + jnp.arange(tk))[None, None, :] >= kvl[:, :, None]
            bias = bias[None] + jnp.where(invalid, NEG_INF, 0.0)
        p = _masked_softmax(_gqa_scores(qc, k, scores_dtype), bias)
        return _gqa_out(p, v)

    if batched_valid or tq <= max(chunk_q, 1) or tq % chunk_q != 0:
        return direct(qs, q_offset + jnp.arange(tq))

    nq = tq // chunk_q

    if window and window + chunk_q < tk:
        # local attention: static-length KV slab per query chunk
        slab = window + chunk_q

        def body(_, i):
            q_lo = i * chunk_q
            qc = lax.dynamic_slice_in_dim(qs, q_lo, chunk_q, axis=1)
            k_lo = jnp.clip(q_lo + chunk_q - slab, 0, tk - slab)
            kc = lax.dynamic_slice_in_dim(k, k_lo, slab, axis=1)
            vc = lax.dynamic_slice_in_dim(v, k_lo, slab, axis=1)
            bias = _mask_bias(
                q_offset + q_lo + jnp.arange(chunk_q),
                k_offset + k_lo + jnp.arange(slab),
                causal=causal, window=window, kv_valid_len=kv_valid_len,
            )
            p = _masked_softmax(_gqa_scores(qc, kc, scores_dtype), bias)
            return None, _gqa_out(p, vc)

        _, chunks = lax.scan(body, None, jnp.arange(nq))
    else:

        def body(_, i):
            q_lo = i * chunk_q
            qc = lax.dynamic_slice_in_dim(qs, q_lo, chunk_q, axis=1)
            out = direct(qc, q_offset + q_lo + jnp.arange(chunk_q))
            return None, out

        _, chunks = lax.scan(body, None, jnp.arange(nq))

    # chunks: [nq, b, chunk_q, hkv, g, dv] -> [b, tq, hkv, g, dv]
    return jnp.moveaxis(chunks, 0, 1).reshape(b, tq, hkv, g, dv)


# ---------------------------------------------------------------------------
# MLPs (TP-sharded hidden dim; caller psums after the down projection)
# ---------------------------------------------------------------------------

def mlp_swiglu(x, wg, wu, wd):
    h = jax.nn.silu(x @ wg) * (x @ wu)
    return h @ wd


def mlp_geglu(x, wg, wu, wd):
    h = jax.nn.gelu(x @ wg, approximate=True) * (x @ wu)
    return h @ wd


def mlp_gelu(x, w1, b1, w2):
    h = jax.nn.gelu(x @ w1 + b1.astype(x.dtype), approximate=True)
    return h @ w2


# ---------------------------------------------------------------------------
# embeddings + vocab-parallel loss
# ---------------------------------------------------------------------------

def embed_lookup(table_local: jax.Array, ids: jax.Array, ctx: Ctx) -> jax.Array:
    """table_local: [vocab, d/tp] (d sharded over model) -> [b, t, d] full."""
    emb_local = jnp.take(table_local, ids, axis=0)
    if ctx.tp == 1:
        return emb_local
    return lax.all_gather(emb_local, ctx.tp_axis, axis=-1, tiled=True)


def tp_cross_entropy(
    logits_local: jax.Array,   # [b, t, V/tp] (vocab sharded over model)
    targets: jax.Array,        # [b, t] int32 global vocab ids
    mask: jax.Array,           # [b, t] 1.0 valid token
    *,
    vocab_real: int,
    vocab_padded: int,
    ctx: Ctx,
) -> jax.Array:
    """Vocab-parallel softmax cross-entropy (Megatron-style), fp32."""
    vl = logits_local.shape[-1]
    lg = logits_local.astype(jnp.float32)
    start = ctx.tp_index() * vl
    col = start + jnp.arange(vl)
    lg = jnp.where(col[None, None, :] < vocab_real, lg, NEG_INF)

    # the stabilizer max carries no gradient (softmax is shift-invariant)
    m_local = lax.stop_gradient(jnp.max(lg, axis=-1))
    m = lax.pmax(m_local, ctx.tp_axis) if ctx.tp > 1 else m_local
    e = jnp.exp(lg - m[..., None])
    denom_local = jnp.sum(e, axis=-1)
    denom = lax.psum(denom_local, ctx.tp_axis) if ctx.tp > 1 else denom_local

    tgt_local = targets - start
    in_range = (tgt_local >= 0) & (tgt_local < vl)
    tgt_logit_local = jnp.take_along_axis(
        lg, jnp.clip(tgt_local, 0, vl - 1)[..., None], axis=-1
    )[..., 0]
    tgt_logit_local = jnp.where(in_range, tgt_logit_local, 0.0)
    tgt_logit = lax.psum(tgt_logit_local, ctx.tp_axis) if ctx.tp > 1 else tgt_logit_local

    nll = jnp.log(denom) + m - tgt_logit
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def tp_psum(x: jax.Array, ctx: Ctx) -> jax.Array:
    return lax.psum(x, ctx.tp_axis) if ctx.tp > 1 else x


def local_head_mask(hq: int, hq_pad: int, hq_local: int, ctx: Ctx) -> jax.Array:
    """1.0 for real Q heads, 0.0 for padded heads, per model rank."""
    if hq == hq_pad:
        return jnp.ones((hq_local,), jnp.float32)
    base = ctx.tp_index() * hq_local if ctx.tp > 1 else 0
    return ((base + jnp.arange(hq_local)) < hq).astype(jnp.float32)
