"""Model registry: ArchConfig -> ModelDef (pools, layouts, apply fns)."""

from __future__ import annotations

import functools
import math

import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.flat_param import LayoutBuilder
from repro.models import blocks as B
from repro.models import recurrent as R
from repro.models.dims import attn_dims, pad_to_tp, shard_dim
from repro.models.lm import ModelDef, Pool


def _embed_pool(cfg: ArchConfig, tp: int) -> Pool:
    b = LayoutBuilder()
    b.add("emb.table", (cfg.vocab, shard_dim(cfg.d_model, tp)), std=0.02)
    if cfg.family == "encdec":
        b.add("emb.pos", (cfg.max_seq, shard_dim(cfg.d_model, tp)), std=0.02)
        b.add("emb.audio_pos", (cfg.n_audio_frames, shard_dim(cfg.d_model, tp)),
              std=0.02)
    return Pool("embed", b.build(), 1, apply=None)


def _head_pool(cfg: ArchConfig, tp: int, vocab_padded: int) -> Pool:
    b = LayoutBuilder()
    d_local = shard_dim(cfg.d_model, tp)
    b.add("final.scale", (d_local,), init="zeros", decay=False,
          model_gather=tp, model_gather_dim=0)
    if cfg.norm == "ln":
        b.add("final.bias", (d_local,), init="zeros", decay=False,
              model_gather=tp, model_gather_dim=0)
    b.add("head.w", (cfg.d_model, vocab_padded // tp), std=1.0 / math.sqrt(cfg.d_model))
    return Pool("head", b.build(), 1, apply=None)


def _wrap(apply):
    """Normalize sub-layer applies to ((x, aux), cache)."""

    def f(t, x, ctx, cache):
        out, nc = apply(t, x, ctx, cache)
        if isinstance(out, tuple):
            return out, nc
        return (out, jnp.float32(0.0)), nc

    return f


def build_model(cfg: ArchConfig, tp: int) -> ModelDef:
    ad = attn_dims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                   cfg.resolved_head_dim, tp)
    vocab_padded = pad_to_tp(cfg.vocab, tp)
    pools: list[Pool] = []

    if cfg.family in ("dense",):
        b = LayoutBuilder()
        B.dense_layer_layout(cfg, tp, b)
        apply = _wrap(lambda t, x, ctx, cache: B.dense_layer_apply(
            cfg, ad, t, x, ctx, cache, window=cfg.window))
        pools.append(Pool(
            "layers", b.build(), cfg.n_layers, apply,
            make_cache=lambda bsz, clen: B.make_kv_cache(
                cfg, tp, bsz, clen, window=cfg.window),
        ))

    elif cfg.family == "moe":
        # the leading dense layers, then the expert layers: a pool each
        make_cache = B.no_latent_cache if cfg.kv_lora_rank else (
            lambda bsz, clen: B.make_kv_cache(cfg, tp, bsz, clen))
        if cfg.first_dense_layers:
            b = LayoutBuilder()
            B.dense_layer_layout(cfg, tp, b, d_ff=cfg.dense_d_ff or cfg.d_ff)
            apply = _wrap(lambda t, x, ctx, cache: B.dense_layer_apply(
                cfg, ad, t, x, ctx, cache))
            pools.append(Pool("dense", b.build(), cfg.first_dense_layers,
                              apply, make_cache=make_cache))
        b = LayoutBuilder()
        B.moe_layer_layout(cfg, tp, b)
        apply = _wrap(lambda t, x, ctx, cache: B.moe_layer_apply(
            cfg, ad, t, x, ctx, cache))
        pools.append(Pool("layers", b.build(), cfg.moe_layers, apply,
                          make_cache=make_cache, aux_shape=(2,)))

    elif cfg.family == "vlm":
        n_self = cfg.cross_interval
        n_super, rem = divmod(cfg.n_layers, n_self + 1)
        if rem:
            raise ValueError("vlm layer count must divide by (interval+1)")
        b = LayoutBuilder()
        for i in range(n_self):
            B.dense_layer_layout(cfg, tp, b, prefix=f"s{i}.")
        B.cross_layer_layout(cfg, tp, b, prefix="x.")

        def apply(t, x, ctx, cache):
            aux = jnp.float32(0.0)
            nc = {}
            for i in range(n_self):
                sub = cache.get(f"s{i}") if cache else None
                x, c = B.dense_layer_apply(cfg, ad, t, x, ctx, sub, prefix=f"s{i}.")
                nc[f"s{i}"] = c
            sub = cache.get("x") if cache else None
            x, c = B.cross_layer_apply(cfg, ad, t, x, ctx, sub, prefix="x.")
            nc["x"] = c
            if all(v is None for v in nc.values()):
                nc = None
            return (x, aux), nc

        def mk_cache(bsz, clen):
            c = {f"s{i}": B.make_kv_cache(cfg, tp, bsz, clen) for i in range(n_self)}
            c["x"] = B.make_cross_cache(cfg, tp, bsz, cfg.n_vision_tokens)
            return c

        pools.append(Pool("layers", b.build(), n_super, apply, mk_cache))

    elif cfg.family == "encdec":
        be = LayoutBuilder()
        B.dense_layer_layout(cfg, tp, be)  # bidirectional self-attn encoder
        enc_apply = _wrap(lambda t, x, ctx, cache: B.dense_layer_apply(
            cfg, ad, t, x, ctx, cache, causal=False))
        pools.append(Pool("enc", be.build(), cfg.n_encoder_layers, enc_apply))
        bd = LayoutBuilder()
        B.encdec_dec_layout(cfg, tp, bd)
        dec_apply = _wrap(lambda t, x, ctx, cache: B.encdec_dec_apply(
            cfg, ad, t, x, ctx, cache))

        def mk_cache(bsz, clen):
            return {
                "self": B.make_kv_cache(cfg, tp, bsz, clen),
                "cross": B.make_cross_cache(cfg, tp, bsz, cfg.n_audio_frames),
            }

        pools.append(Pool("dec", bd.build(), cfg.n_layers, dec_apply, mk_cache))

    elif cfg.family == "griffin":
        pattern = cfg.pattern or ("rec", "rec", "attn")
        n_super, rem = divmod(cfg.n_layers, len(pattern))
        pools.extend(_griffin_pools(cfg, tp, ad, pattern, n_super, "g"))
        if rem:
            pools.extend(_griffin_pools(cfg, tp, ad, pattern[:rem], 1, "gtail"))

    elif cfg.family == "xlstm":
        every = cfg.slstm_every or 4
        pattern = ("m",) * (every - 1) + ("s",)
        n_super, rem = divmod(cfg.n_layers, len(pattern))
        pools.extend(_xlstm_pools(cfg, tp, pattern, n_super, "x"))
        if rem:
            pools.extend(_xlstm_pools(cfg, tp, ("m",) * rem, 1, "xtail"))

    else:
        raise ValueError(f"unknown family {cfg.family}")

    return ModelDef(
        cfg=cfg, tp=tp, pools=tuple(pools),
        embed=_embed_pool(cfg, tp),
        head=_head_pool(cfg, tp, vocab_padded),
        vocab_padded=vocab_padded,
    )


def _griffin_pools(cfg, tp, ad, pattern, stack, name):
    b = LayoutBuilder()
    kinds = []
    counts = {"rec": 0, "attn": 0}
    for kind in pattern:
        i = counts[kind]
        counts[kind] += 1
        prefix = f"{kind}{i}."
        kinds.append((kind, prefix))
        if kind == "rec":
            R.griffin_rec_layout(cfg, tp, b, prefix=prefix)
        else:
            B.dense_layer_layout(cfg, tp, b, prefix=prefix)

    def apply(t, x, ctx, cache):
        nc = {}
        for kind, prefix in kinds:
            sub = cache.get(prefix) if cache else None
            if kind == "rec":
                x, c = R.griffin_rec_apply(cfg, t, x, ctx, sub, prefix=prefix)
            else:
                x, c = B.dense_layer_apply(
                    cfg, ad, t, x, ctx, sub, prefix=prefix, window=cfg.window)
            nc[prefix] = c
        if all(v is None for v in nc.values()):
            nc = None
        return (x, jnp.float32(0.0)), nc

    def mk_cache(bsz, clen):
        c = {}
        for kind, prefix in kinds:
            if kind == "rec":
                c[prefix] = R.make_rec_cache(cfg, tp, bsz)
            else:
                c[prefix] = B.make_kv_cache(cfg, tp, bsz, clen, window=cfg.window)
        return c

    return [Pool(name, b.build(), stack, apply, mk_cache)]


def _xlstm_pools(cfg, tp, pattern, stack, name):
    b = LayoutBuilder()
    kinds = []
    counts = {"m": 0, "s": 0}
    for kind in pattern:
        i = counts[kind]
        counts[kind] += 1
        prefix = f"{kind}{i}."
        kinds.append((kind, prefix))
        if kind == "m":
            R.mlstm_layout(cfg, tp, b, prefix=prefix)
        else:
            R.slstm_layout(cfg, tp, b, prefix=prefix)

    def apply(t, x, ctx, cache):
        nc = {}
        for kind, prefix in kinds:
            sub = cache.get(prefix) if cache else None
            if kind == "m":
                x, c = R.mlstm_apply(cfg, t, x, ctx, sub, prefix=prefix)
            else:
                x, c = R.slstm_apply(cfg, t, x, ctx, sub, prefix=prefix)
            nc[prefix] = c
        if all(v is None for v in nc.values()):
            nc = None
        return (x, jnp.float32(0.0)), nc

    def mk_cache(bsz, clen):
        c = {}
        for kind, prefix in kinds:
            c[prefix] = (R.make_mlstm_cache(cfg, bsz) if kind == "m"
                         else R.make_slstm_cache(cfg, bsz))
        return c

    return [Pool(name, b.build(), stack, apply, mk_cache)]


# ---------------------------------------------------------------------------
# parameter accounting (for the partition heuristic + MODEL_FLOPS)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _counts(cfg: ArchConfig) -> tuple[int, int]:
    model = build_model(cfg, tp=1)
    total = 0
    active = 0
    for pool in model.all_pools():
        for seg in pool.layout.segments:
            n = seg.size * pool.stack
            total += n
            if seg.name.startswith("moe."):
                active += int(n * cfg.top_k / max(cfg.n_experts, 1))
            else:
                active += n
    return total, active


def exact_param_count(cfg: ArchConfig) -> int:
    return _counts(cfg)[0]


def active_param_count(cfg: ArchConfig) -> int:
    return _counts(cfg)[1]
