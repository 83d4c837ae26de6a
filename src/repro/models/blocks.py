"""Transformer layer types: dense (llama/qwen/granite/yi), cross-attention
(llama-3.2-vision), encoder/decoder (whisper), MoE (deepseek, dbrx), and
multi-head latent attention (deepseek-v2).

Each layer type provides
  * ``*_layout(cfg, tp, b)``   — appends its segments to a LayoutBuilder
  * ``*_apply(t, x, ctx, ...)``— pure function over unflattened tensors
  * cache constructors for decode.

Weights are stored TP-local (see models/dims.py for the KV-gather scheme);
activations are full ``d_model`` per rank, with a ``psum('model')`` after the
attention output and MLP down projections (Megatron TP).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes
from repro.configs.base import ArchConfig
from repro.core import quant as Q
from repro.core.flat_param import LayoutBuilder
from repro.models import layers as L
from repro.models.dims import AttnDims, attn_dims, shard_dim


# ---------------------------------------------------------------------------
# attention sub-block
# ---------------------------------------------------------------------------

def attn_layout(
    cfg: ArchConfig, tp: int, b: LayoutBuilder, prefix: str = "attn.",
    *, bias: bool = False, kv_input_dim: int | None = None,
):
    ad = attn_dims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, tp)
    d = cfg.d_model
    kvd = kv_input_dim or d
    std = 1.0 / math.sqrt(d)
    out_std = 1.0 / math.sqrt(ad.hq_pad * ad.head_dim) / math.sqrt(2 * cfg.n_layers)
    b.add(prefix + "wq", (d, ad.q_cols_local), std=std)
    b.add(prefix + "wk", (kvd, ad.kv_cols_stored), std=std,
          model_gather=ad.kv_gather, model_gather_dim=1)
    b.add(prefix + "wv", (kvd, ad.kv_cols_stored), std=std,
          model_gather=ad.kv_gather, model_gather_dim=1)
    b.add(prefix + "wo", (ad.q_cols_local, d), std=out_std)
    if bias:
        b.add(prefix + "bq", (ad.q_cols_local,), init="zeros", decay=False)
        b.add(prefix + "bk", (ad.kv_cols_stored,), init="zeros", decay=False,
              model_gather=ad.kv_gather, model_gather_dim=0)
        b.add(prefix + "bv", (ad.kv_cols_stored,), init="zeros", decay=False,
              model_gather=ad.kv_gather, model_gather_dim=0)
        b.add(prefix + "bo", (shard_dim(d, tp),), init="zeros", decay=False,
              model_gather=tp, model_gather_dim=0)
    return ad


def attn_qkv(t, x, kv_x, ad: AttnDims, ctx: L.Ctx, prefix: str, *, bias: bool):
    """Project to q [b,t,hkv_local,g,dh], k/v [b,t,hkv_local,dh]."""
    bsz, tq, _ = x.shape
    tk = kv_x.shape[1]
    q = x @ t[prefix + "wq"]
    k = kv_x @ t[prefix + "wk"]
    v = kv_x @ t[prefix + "wv"]
    if bias:
        q = q + t[prefix + "bq"].astype(q.dtype)
        k = k + t[prefix + "bk"].astype(k.dtype)
        v = v + t[prefix + "bv"].astype(v.dtype)
    q = q.reshape(bsz, tq, ad.hkv_local, ad.q_per_kv_local, ad.head_dim)
    k = k.reshape(bsz, tk, ad.hkv_local, ad.head_dim)
    v = v.reshape(bsz, tk, ad.hkv_local, ad.head_dim)
    return q, k, v


def attn_out(t, attn: jax.Array, ad: AttnDims, ctx: L.Ctx, prefix: str, *, bias: bool):
    """attn [b,t,hkv_local,g,dh] -> [b,t,d] (full, post-psum)."""
    bsz, tq = attn.shape[:2]
    hmask = L.local_head_mask(ad.hq, ad.hq_pad, ad.hq_local, ctx)
    attn = attn * hmask.reshape(1, 1, ad.hkv_local, ad.q_per_kv_local, 1).astype(attn.dtype)
    out = attn.reshape(bsz, tq, ad.q_cols_local) @ t[prefix + "wo"]
    out = L.tp_psum(out, ctx)
    if bias:
        out = out + t[prefix + "bo"].astype(out.dtype)
    return out


def _paged_kv_write(cache, pages, k, v, absp, valid_tok):
    """Scatter this tick's k/v token rows into the paged block pool.

    cache: {"k","v"[,"ks","vs"]} with k/v [n_blocks, block_size, h, dh]
    (int8 pools add f32 scale pages [n_blocks, block_size, n_scale]);
    k/v [b, tq, h, dh]; absp [b, tq] absolute positions; valid_tok [b, tq].
    Padding rows are redirected out of range and dropped (``mode="drop"``),
    so a chunk never corrupts blocks it does not own.  Int8 pools quantize
    each token row against its own per-128-block absmax (the qgZ scheme) —
    blocks are only ever written incrementally, never re-quantized.
    """
    nb, bs_blk = cache["k"].shape[:2]
    bidx = jnp.arange(absp.shape[0])[:, None]
    blk = pages.block_tables[bidx, absp // bs_blk]
    blk = jnp.where(valid_tok, blk, nb)  # out-of-range -> dropped
    off = absp % bs_blk
    new = dict(cache)
    if "ks" in cache:
        # Scales are per (token, head, 128-block of head_dim) so the scale
        # pages shard over the model axis exactly like the k/v pages.
        qk, sk = Q.quantize_flat(k.astype(jnp.float32))
        qv, sv = Q.quantize_flat(v.astype(jnp.float32))
        new["k"] = cache["k"].at[blk, off].set(qk, mode="drop")
        new["v"] = cache["v"].at[blk, off].set(qv, mode="drop")
        new["ks"] = cache["ks"].at[blk, off].set(sk, mode="drop")
        new["vs"] = cache["vs"].at[blk, off].set(sv, mode="drop")
    else:
        new["k"] = cache["k"].at[blk, off].set(k.astype(cache["k"].dtype), mode="drop")
        new["v"] = cache["v"].at[blk, off].set(v.astype(cache["v"].dtype), mode="drop")
    return new


def _paged_kv_read(cache, pages, compute_dtype):
    """Gather the block pool into a contiguous [b, max_blocks*bs, h, dh] view.

    The view has the same key-axis length as a contiguous cache of capacity
    ``max_blocks * block_size``, and unwritten tail entries are masked by
    ``kv_valid_len`` — masked lanes underflow to exactly 0.0 in the fp32
    softmax, which is what makes paged decode bitwise-equal to the
    contiguous reference.
    """
    tables = pages.block_tables
    b, mb = tables.shape
    nb, bs_blk, h, dh = cache["k"].shape

    def view(name):
        pagev = cache[name][tables]  # [b, mb, bs, ...]
        return pagev.reshape(b, mb * bs_blk, *pagev.shape[3:])

    k, v = view("k"), view("v")
    if "ks" in cache:
        k = Q.dequantize_flat(k, view("ks"), dtype=compute_dtype)
        v = Q.dequantize_flat(v, view("vs"), dtype=compute_dtype)
    return k, v


@jax.named_scope(scopes.ATTENTION)
def self_attention(
    t, x, ctx: L.Ctx, ad: AttnDims, cfg: ArchConfig, *,
    prefix: str = "attn.", causal: bool = True, window: int = 0,
    use_rope: bool = True, bias: bool = False, cache=None,
):
    """Self attention in train/prefill/decode modes.

    cache: None (train) or dict(k, v[, pos]) for prefill-fill / decode.
    Returns (out, new_cache).
    """
    bsz, tq, _ = x.shape
    q, k, v = attn_qkv(t, x, x, ad, ctx, prefix, bias=bias)

    if ctx.mode == "decode" and (ctx.pages is not None or getattr(ctx.pos, "ndim", 0)):
        # Continuous batching: per-request positions [b] (ragged batch),
        # optionally over a paged block pool.  tq > 1 means a chunk of
        # tokens per slot (chunked prefill interleaved with decode); rows
        # at or beyond a slot's n_new are padding whose writes are dropped
        # and whose outputs the scheduler ignores.
        if window:
            raise NotImplementedError("paged/vector-position decode needs window == 0")
        pos, pages = ctx.pos, ctx.pages
        absp = pos[:, None] + jnp.arange(tq)[None, :]  # [b, tq]
        if use_rope:
            q = _rope5(q, absp, cfg.rope_theta)
            k = L.rotary(k, absp, cfg.rope_theta)
        n_new = getattr(pages, "n_new", None) if pages is not None else None
        valid_tok = (jnp.arange(tq)[None, :] < n_new[:, None]) if n_new is not None \
            else jnp.ones((bsz, tq), bool)
        if pages is not None:
            new_cache = _paged_kv_write(cache, pages, k, v, absp, valid_tok)
            k_all, v_all = _paged_kv_read(new_cache, pages, ctx.compute_dtype)
        else:
            cap = cache["k"].shape[1]
            bidx = jnp.arange(bsz)[:, None]
            slot = jnp.where(valid_tok, absp, cap)  # out-of-range -> dropped
            k_all = cache["k"].at[bidx, slot].set(k.astype(cache["k"].dtype), mode="drop")
            v_all = cache["v"].at[bidx, slot].set(v.astype(cache["v"].dtype), mode="drop")
            new_cache = {"k": k_all, "v": v_all}
        out = L.attention(
            q, k_all, v_all, causal=False, window=0,
            kv_valid_len=absp + 1, scores_dtype=ctx.scores_dtype,
        )
        # a cache dtype wider than the compute dtype (fp32 KV under bf16
        # compute) must not leak into the residual stream's scan carry
        out = out.astype(x.dtype)
        return attn_out(t, out, ad, ctx, prefix, bias=bias), new_cache

    if ctx.mode == "decode":
        pos = ctx.pos
        positions = jnp.broadcast_to(pos, (bsz, tq))
        if use_rope:
            q = _rope5(q, positions, cfg.rope_theta)
            k = L.rotary(k, positions, cfg.rope_theta)
        cap = cache["k"].shape[1]
        slot = pos % cap if window else pos
        k_cache = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        v_cache = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        valid = jnp.minimum(pos + 1, cap)
        out = L.attention(
            q, k_cache, v_cache, causal=False, window=0,
            kv_valid_len=valid, scores_dtype=ctx.scores_dtype,
        )
        new_cache = {"k": k_cache, "v": v_cache}
        return attn_out(t, out, ad, ctx, prefix, bias=bias), new_cache

    positions = jnp.broadcast_to(jnp.arange(tq), (bsz, tq))
    if use_rope:
        q = _rope5(q, positions, cfg.rope_theta)
        k = L.rotary(k, positions, cfg.rope_theta)
    out = L.attention(q, k, v, causal=causal, window=window,
                      scores_dtype=ctx.scores_dtype)
    new_cache = None
    if ctx.mode == "prefill":
        cap = ctx.cache_len if not window else min(window, ctx.cache_len)
        if tq >= cap:
            # slot of absolute position a is a % cap (matches decode writes)
            k_keep = jnp.roll(k[:, tq - cap:], tq % cap, axis=1)
            v_keep = jnp.roll(v[:, tq - cap:], tq % cap, axis=1)
        else:
            pad = cap - tq
            k_keep = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_keep = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        new_cache = {"k": k_keep.astype(ctx.compute_dtype),
                     "v": v_keep.astype(ctx.compute_dtype)}
    return attn_out(t, out, ad, ctx, prefix, bias=bias), new_cache


@jax.named_scope(scopes.ATTENTION)
def cross_attention(
    t, x, kv_src, ctx: L.Ctx, ad: AttnDims, cfg: ArchConfig, *,
    prefix: str = "xattn.", bias: bool = False, cache=None,
):
    """Cross attention against a precomputed source (vision / encoder).

    During decode the projected source KV comes from the cache (computed at
    prefill) to keep the per-token cost O(1) in projections.
    """
    bsz, tq, _ = x.shape
    if ctx.mode == "decode" and cache is not None:
        q = x @ t[prefix + "wq"]
        if bias:
            q = q + t[prefix + "bq"].astype(q.dtype)
        q = q.reshape(bsz, tq, ad.hkv_local, ad.q_per_kv_local, ad.head_dim)
        k, v = cache["k"], cache["v"]
        out = L.attention(q, k, v, causal=False, scores_dtype=ctx.scores_dtype)
        return attn_out(t, out, ad, ctx, prefix, bias=bias), cache
    q, k, v = attn_qkv(t, x, kv_src, ad, ctx, prefix, bias=bias)
    out = L.attention(q, k, v, causal=False, scores_dtype=ctx.scores_dtype)
    new_cache = None
    if ctx.mode == "prefill":
        new_cache = {"k": k.astype(ctx.compute_dtype), "v": v.astype(ctx.compute_dtype)}
    return attn_out(t, out, ad, ctx, prefix, bias=bias), new_cache


def _rope5(q: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary over [b, t, hkv, g, dh] (fold grouped head dims)."""
    b, tq, hkv, g, dh = q.shape
    out = L.rotary(q.reshape(b, tq, hkv * g, dh), positions, theta)
    return out.reshape(b, tq, hkv, g, dh)


def make_kv_cache(cfg: ArchConfig, tp: int, batch: int, cache_len: int, *, window: int = 0):
    ad = attn_dims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, tp)
    cap = min(window, cache_len) if window else cache_len
    shape = (batch, cap, ad.hkv_local, ad.head_dim)
    return {"k": jnp.zeros(shape, jnp.bfloat16), "v": jnp.zeros(shape, jnp.bfloat16)}


def make_cross_cache(cfg: ArchConfig, tp: int, batch: int, src_len: int):
    ad = attn_dims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, tp)
    shape = (batch, src_len, ad.hkv_local, ad.head_dim)
    return {"k": jnp.zeros(shape, jnp.bfloat16), "v": jnp.zeros(shape, jnp.bfloat16)}


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2 §2.1), train path
# ---------------------------------------------------------------------------

def mla_layout(cfg: ArchConfig, tp: int, b: LayoutBuilder, prefix: str = "attn."):
    """Heads are split over the model axis; the joint down projection and
    the latent's norm are used whole by every head, so they are stored
    sharded and gathered at use, like the router."""
    d, h = cfg.d_model, shard_dim(cfg.n_heads, tp, "n_heads")
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    dr, dv = cfg.qk_rope_head_dim, cfg.v_head_dim
    std = 1.0 / math.sqrt(d)
    out_std = 1.0 / math.sqrt(cfg.n_heads * dv) / math.sqrt(2 * cfg.n_layers)
    b.add(prefix + "wq", (d, h * (dn + dr)), std=std)
    b.add(prefix + "wkv_a", (d, shard_dim(r + dr, tp, "kv_lora_rank + rope")),
          std=std, model_gather=tp, model_gather_dim=1)
    b.add(prefix + "kv_norm.scale", (shard_dim(r, tp, "kv_lora_rank"),),
          init="zeros", decay=False, model_gather=tp, model_gather_dim=0)
    b.add(prefix + "wkv_b", (r, h * (dn + dv)), std=1.0 / math.sqrt(r))
    b.add(prefix + "wo", (h * dv, d), std=out_std)


def mla_scale(cfg: ArchConfig) -> float:
    """The score scale: qk head size^-1/2, times YaRN's mscale squared
    (DeepSeek-V2's ``softmax_scale``)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_rope(cfg: ArchConfig, x, positions):
    """Rotary over the rope part [b, t, h, dr], YaRN-scaled where set."""
    if not cfg.yarn_factor:
        return L.rotary(x, positions, cfg.rope_theta)
    inv = L.yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn_factor,
                          cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                          cfg.yarn_beta_slow)
    out = L.rotary(x, positions, cfg.rope_theta, inv)
    m = (L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
         / L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return out if m == 1.0 else (out.astype(jnp.float32) * m).astype(out.dtype)


@jax.named_scope(scopes.ATTENTION)
def mla_attention(t, x, ctx: L.Ctx, cfg: ArchConfig, prefix: str = "attn."):
    """Causal multi-head latent attention over a whole sequence (training).

    q = x Wq ([nope | rope] per head); [c | k_rope] = x Wkv_a, the latent c
    RMS-normed, [k_nope | v] = c Wkv_b per head; the rope key is one for all
    heads.  Scores over [nope | rope] (192 wide in DeepSeek-V2), values of
    ``v_head_dim``.  Rope uses the rotate-half layout.
    """
    if ctx.mode != "train":
        raise NotImplementedError(
            "MLA runs the train path only: prefill and decode need its "
            "latent cache, which the serving path does not have")
    bsz, tq, _ = x.shape
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    dr, dv = cfg.qk_rope_head_dim, cfg.v_head_dim
    h = t[prefix + "wq"].shape[1] // (dn + dr)
    q = (x @ t[prefix + "wq"]).reshape(bsz, tq, h, dn + dr)
    kv = x @ t[prefix + "wkv_a"]
    latent = L.rms_norm(kv[..., :r], t[prefix + "kv_norm.scale"])
    kvb = (latent @ t[prefix + "wkv_b"]).reshape(bsz, tq, h, dn + dv)
    positions = jnp.broadcast_to(jnp.arange(tq), (bsz, tq))
    q_rope = _mla_rope(cfg, q[..., dn:], positions)
    k_rope = _mla_rope(cfg, kv[:, :, None, r:], positions)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_rope, (bsz, tq, h, dr))], -1)
    out = L.attention(q[:, :, :, None], k, kvb[..., dn:], scale=mla_scale(cfg),
                      causal=True, scores_dtype=ctx.scores_dtype)
    out = out.reshape(bsz, tq, h * dv) @ t[prefix + "wo"]
    return L.tp_psum(out, ctx)


def no_latent_cache(*_):
    raise NotImplementedError(
        "MLA has no decode cache yet: its latent cache is not built")


def self_attn_layout(cfg: ArchConfig, tp: int, b: LayoutBuilder):
    """A layer's self attention: MLA where ``kv_lora_rank`` is set."""
    if cfg.kv_lora_rank:
        mla_layout(cfg, tp, b)
    else:
        attn_layout(cfg, tp, b, "attn.", bias=cfg.qkv_bias)


def self_attn(cfg: ArchConfig, ad: AttnDims, t, x, ctx: L.Ctx, cache=None, *,
              window: int = 0, causal: bool = True):
    """The self attention ``self_attn_layout`` laid out: (out, new_cache)."""
    if cfg.kv_lora_rank:
        return mla_attention(t, x, ctx, cfg), None
    return self_attention(
        t, x, ctx, ad, cfg, prefix="attn.", causal=causal, window=window,
        use_rope=cfg.use_rope, bias=cfg.qkv_bias, cache=cache)


# ---------------------------------------------------------------------------
# norms + MLP sub-blocks
# ---------------------------------------------------------------------------

def norm_layout(cfg: ArchConfig, tp: int, b: LayoutBuilder, name: str):
    d_local = shard_dim(cfg.d_model, tp)
    b.add(name + ".scale", (d_local,), init="zeros", decay=False,
          model_gather=tp, model_gather_dim=0)
    if cfg.norm == "ln":
        b.add(name + ".bias", (d_local,), init="zeros", decay=False,
              model_gather=tp, model_gather_dim=0)


def apply_norm(cfg: ArchConfig, t, x, name: str):
    if cfg.norm == "ln":
        return L.layer_norm(x, t[name + ".scale"], t[name + ".bias"])
    return L.rms_norm(x, t[name + ".scale"])


def mlp_layout(cfg: ArchConfig, tp: int, b: LayoutBuilder, prefix: str = "mlp.",
               d_ff: int | None = None):
    d = cfg.d_model
    f_local = shard_dim(d_ff or cfg.d_ff, tp, "d_ff")
    std = 1.0 / math.sqrt(d)
    dstd = 1.0 / math.sqrt((d_ff or cfg.d_ff)) / math.sqrt(2 * cfg.n_layers)
    if cfg.mlp in ("swiglu", "geglu"):
        b.add(prefix + "wg", (d, f_local), std=std)
        b.add(prefix + "wu", (d, f_local), std=std)
        b.add(prefix + "wd", (f_local, d), std=dstd)
    else:  # gelu (whisper)
        b.add(prefix + "w1", (d, f_local), std=std)
        b.add(prefix + "b1", (f_local,), init="zeros", decay=False)
        b.add(prefix + "wd", (f_local, d), std=dstd)
        b.add(prefix + "b2", (shard_dim(d, tp),), init="zeros", decay=False,
              model_gather=tp, model_gather_dim=0)


@jax.named_scope(scopes.MLP)
def mlp_apply(cfg: ArchConfig, t, x, ctx: L.Ctx, prefix: str = "mlp."):
    if cfg.mlp == "swiglu":
        out = L.mlp_swiglu(x, t[prefix + "wg"], t[prefix + "wu"], t[prefix + "wd"])
    elif cfg.mlp == "geglu":
        out = L.mlp_geglu(x, t[prefix + "wg"], t[prefix + "wu"], t[prefix + "wd"])
    else:
        out = L.mlp_gelu(x, t[prefix + "w1"], t[prefix + "b1"], t[prefix + "wd"])
    out = L.tp_psum(out, ctx)
    if cfg.mlp == "gelu":
        out = out + t[prefix + "b2"].astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# dense decoder layer (llama / qwen / granite / yi family)
# ---------------------------------------------------------------------------

def dense_layer_layout(cfg: ArchConfig, tp: int, b: LayoutBuilder, prefix: str = "",
                       d_ff: int | None = None):
    pb = LayoutBuilder(prefix)
    norm_layout(cfg, tp, pb, "ln1")
    self_attn_layout(cfg, tp, pb)
    norm_layout(cfg, tp, pb, "ln2")
    mlp_layout(cfg, tp, pb, "mlp.", d_ff=d_ff)
    b.extend(pb)


def dense_layer_apply(cfg: ArchConfig, ad: AttnDims, t, x, ctx: L.Ctx,
                      cache=None, prefix: str = "", *, window: int = 0,
                      causal: bool = True):
    tt = {name[len(prefix):]: v for name, v in t.items()} if prefix else t
    h = apply_norm(cfg, tt, x, "ln1")
    a, new_cache = self_attn(cfg, ad, tt, h, ctx, cache, window=window,
                             causal=causal)
    x = x + a
    h = apply_norm(cfg, tt, x, "ln2")
    x = x + mlp_apply(cfg, tt, h, ctx, "mlp.")
    return x, new_cache


# ---------------------------------------------------------------------------
# gated cross-attention layer (llama-3.2-vision)
# ---------------------------------------------------------------------------

def cross_layer_layout(cfg: ArchConfig, tp: int, b: LayoutBuilder, prefix: str = ""):
    pb = LayoutBuilder(prefix)
    norm_layout(cfg, tp, pb, "ln1")
    attn_layout(cfg, tp, pb, "xattn.")
    pb.add("gate_attn", (1,), init="zeros", decay=False)
    norm_layout(cfg, tp, pb, "ln2")
    mlp_layout(cfg, tp, pb, "mlp.")
    pb.add("gate_mlp", (1,), init="zeros", decay=False)
    b.extend(pb)


def cross_layer_apply(cfg: ArchConfig, ad: AttnDims, t, x, ctx: L.Ctx,
                      cache=None, prefix: str = ""):
    tt = {name[len(prefix):]: v for name, v in t.items()} if prefix else t
    h = apply_norm(cfg, tt, x, "ln1")
    a, new_cache = cross_attention(
        tt, h, ctx.vision if ctx.vision is not None else ctx.enc_out,
        ctx, ad, cfg, prefix="xattn.", cache=cache)
    x = x + jnp.tanh(tt["gate_attn"].astype(jnp.float32)).astype(x.dtype) * a
    h = apply_norm(cfg, tt, x, "ln2")
    m = mlp_apply(cfg, tt, h, ctx, "mlp.")
    x = x + jnp.tanh(tt["gate_mlp"].astype(jnp.float32)).astype(x.dtype) * m
    return x, new_cache


# ---------------------------------------------------------------------------
# whisper encoder / decoder layers
# ---------------------------------------------------------------------------

def encdec_dec_layout(cfg: ArchConfig, tp: int, b: LayoutBuilder, prefix: str = ""):
    pb = LayoutBuilder(prefix)
    norm_layout(cfg, tp, pb, "ln1")
    attn_layout(cfg, tp, pb, "attn.", bias=True)
    norm_layout(cfg, tp, pb, "lnx")
    attn_layout(cfg, tp, pb, "xattn.", bias=True)
    norm_layout(cfg, tp, pb, "ln2")
    mlp_layout(cfg, tp, pb, "mlp.")
    b.extend(pb)


def encdec_dec_apply(cfg: ArchConfig, ad: AttnDims, t, x, ctx: L.Ctx,
                     cache=None, prefix: str = ""):
    tt = {name[len(prefix):]: v for name, v in t.items()} if prefix else t
    self_cache = cache.get("self") if cache else None
    cross_cache = cache.get("cross") if cache else None
    h = apply_norm(cfg, tt, x, "ln1")
    a, nc_self = self_attention(
        tt, h, ctx, ad, cfg, prefix="attn.", causal=True,
        use_rope=False, bias=True, cache=self_cache)
    x = x + a
    h = apply_norm(cfg, tt, x, "lnx")
    a, nc_cross = cross_attention(
        tt, h, ctx.enc_out, ctx, ad, cfg, prefix="xattn.", bias=True,
        cache=cross_cache)
    x = x + a
    h = apply_norm(cfg, tt, x, "ln2")
    x = x + mlp_apply(cfg, tt, h, ctx, "mlp.")
    new_cache = None
    if nc_self is not None or nc_cross is not None:
        new_cache = {"self": nc_self, "cross": nc_cross}
    return x, new_cache


# ---------------------------------------------------------------------------
# MoE layer (deepseek-moe / deepseek-v2 / dbrx)
# ---------------------------------------------------------------------------

def moe_layer_layout(cfg: ArchConfig, tp: int, b: LayoutBuilder, prefix: str = ""):
    """The router scores all ``n_experts``; the layer holds the first
    ``held_experts`` of them, split over the model axis."""
    pb = LayoutBuilder(prefix)
    norm_layout(cfg, tp, pb, "ln1")
    self_attn_layout(cfg, tp, pb)
    norm_layout(cfg, tp, pb, "ln2")
    d, f = cfg.d_model, cfg.d_ff
    e_local = shard_dim(cfg.held_experts, tp, "held experts")
    std = 1.0 / math.sqrt(d)
    dstd = 1.0 / math.sqrt(f) / math.sqrt(2 * cfg.n_layers)
    pb.add("router.w", (d, shard_dim(cfg.n_experts, tp, "n_experts")), std=std,
           model_gather=tp, model_gather_dim=1)
    pb.add("moe.wg", (e_local, d, f), std=std)
    pb.add("moe.wu", (e_local, d, f), std=std)
    pb.add("moe.wd", (e_local, f, d), std=dstd)
    if cfg.n_shared_experts:
        mlp_layout(cfg, tp, pb, "shared.", d_ff=cfg.n_shared_experts * f)
    b.extend(pb)


def moe_routed(x2d, t, cfg: ArchConfig, ctx: L.Ctx):
    """The routed experts' part of the layer that the experts held on this
    model rank give, dropping no token.

    x2d: [n, d] tokens.  Every token takes its top-k of all ``n_experts``
    router outputs (softmax, then renormalised where ``norm_topk_prob``).
    The n*k assignments are put in expert order, those to experts not held
    here last, and the held experts run as grouped products over their own
    rows (``lax.ragged_dot``): no capacity, no [experts, capacity, d]
    buffer.  What absent experts would add is left out.  Returns (y [n, d],
    aux [2]): the switch-style balance loss over all experts, and the held
    experts' largest assignment count over their mean.
    """
    n, d = x2d.shape
    k = cfg.top_k
    e_local = t["moe.wg"].shape[0]
    with jax.named_scope(scopes.MOE_ROUTE):
        logits = jnp.dot(x2d.astype(jnp.float32),
                         t["router.w"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)       # [n, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = lax.top_k(probs, k)                         # [n, k]
        if cfg.norm_topk_prob:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        slot = idx.reshape(-1) - ctx.tp_index() * e_local       # [n*k]
        held = (slot >= 0) & (slot < e_local)
        slot = jnp.where(held, slot, e_local)                   # absent: last
        order = jnp.argsort(slot, stable=True)
        sizes = jnp.bincount(slot, length=e_local + 1)[:e_local].astype(jnp.int32)
        rows = x2d[order // k]                                  # expert order
    with jax.named_scope(scopes.MOE_EXPERTS):
        # The TPU's ragged dot leaves the rows past the groups undefined, in
        # its transposes too: zero them, and so their cotangents.
        live = (jnp.arange(n * k) < jnp.sum(sizes))[:, None]

        def grouped(a, w):
            return jnp.where(live, lax.ragged_dot(a, w, sizes), 0)

        rows = jnp.where(live, rows, 0)
        h = grouped(rows, t["moe.wg"])
        u = grouped(rows, t["moe.wu"])
        out = grouped(jax.nn.silu(h) * u, t["moe.wd"])
    with jax.named_scope(scopes.MOE_ROUTE):
        back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
        w = jnp.where(held, gate.reshape(-1), 0.0).reshape(n, k)
        y = jnp.einsum("nkd,nk->nd", out[back].reshape(n, k, d),
                       w.astype(out.dtype),
                       preferred_element_type=jnp.float32).astype(x2d.dtype)
        e = probs.shape[-1]
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
        load = sizes.astype(jnp.float32)
        aux = jnp.stack([e * jnp.sum(me * ce),
                         jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9)])
    return y, aux


@jax.named_scope(scopes.MLP)
def moe_ffn(t, x, cfg: ArchConfig, ctx: L.Ctx):
    """Routed experts plus shared experts.  Activations are whole on every
    model rank, so each rank routes all tokens and runs its own experts;
    the ranks' parts add up over the model axis (expert parallelism with no
    token exchange).  Returns (out [b, s, d], aux [2], see moe_routed)."""
    b, s, d = x.shape
    y, aux = moe_routed(x.reshape(b * s, d), t, cfg, ctx)
    out = L.tp_psum(y, ctx).reshape(b, s, d)
    if cfg.n_shared_experts:
        out = out + mlp_apply(cfg, t, x, ctx, "shared.")
    return out, aux


def moe_layer_apply(cfg: ArchConfig, ad: AttnDims, t, x, ctx: L.Ctx,
                    cache=None, prefix: str = ""):
    tt = {name[len(prefix):]: v for name, v in t.items()} if prefix else t
    h = apply_norm(cfg, tt, x, "ln1")
    a, new_cache = self_attn(cfg, ad, tt, h, ctx, cache)
    x = x + a
    h = apply_norm(cfg, tt, x, "ln2")
    y, aux = moe_ffn(tt, h, cfg, ctx)
    x = x + y
    return (x, aux), new_cache
