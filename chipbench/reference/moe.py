"""Plain DeepSeek-V2 decoder in float32, for the expert cell.

Forward pass, token cross-entropy, gradients and clipped AdamW, written from
the configuration file's numbers (its Hugging Face keys) alone: it imports
nothing of the program, and takes the optimizer's schedule, the norms and
the numerics from ``reference/dense.py``.  It draws its own weights from the
seed (``init_params``), and the harness hands the same draws to the program.

The model (arXiv:2405.04434, the ``deepseek_v2`` modelling code):

* RMSNorm (``rms_norm_eps``) with a gain, before attention and before the
  MLP; residual adds;
* multi-head latent attention without a query latent: q = h Wq per head
  [nope | rope]; [c | k_rope] = h Wkv_a; [k_nope | v] = RMSNorm(c) Wkv_b per
  head; one rope key for all heads; YaRN rope frequencies and the softmax
  scale (qk head size)^-1/2 times YaRN's mscale squared; causal softmax;
* the first ``first_k_dense_replace`` layers a SwiGLU MLP of width
  ``intermediate_size``, the rest an expert layer: a softmax router over
  ``published.n_routed_experts`` outputs, greedy top-``num_experts_per_tok``,
  gates renormalised only where ``norm_topk_prob``, times
  ``routed_scaling_factor``; SwiGLU experts of width
  ``moe_intermediate_size``; shared experts as one SwiGLU of width
  ``n_shared_experts`` x that.

Departures, each deliberate:

* the expert share: only experts 0 .. ``n_routed_experts``-1 are held; the
  router still scores all the published experts, and what an absent expert
  would add is left out, as in the program.  The held experts are an
  explicit loop, each applied to every token and weighted by its gate where
  the token chose it (0 elsewhere): no sorting, no capacity;
* the vocabulary is the configuration's slice (``vocab_size``);
* rope rotates halves (Llama's layout), where DeepSeek rotates interleaved
  pairs: on random weights a fixed permutation of the rope columns of Wq and
  Wkv_a;
* the balance loss is the program's term, not DeepSeek's sequence-level
  ``aux_loss_alpha`` term: per layer, E x sum_e (mean router probability of
  e) x (share of tokens whose first choice is e), over the tokens of one
  micro-batch on one device, averaged over the devices, weighted by
  ``router_aux_weight`` (the configuration's ``assumed`` says so);
* attention runs in blocks of ``ATTN_BLOCK`` query rows, each under
  ``jax.checkpoint``, so that it fits at 4096 tokens.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from chipbench.reference.dense import (  # noqa: F401  (FP8: the control)
    ATTN_BLOCK, FP8, FP32, Numerics, lr_at, norms,
)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def routed(cfg: dict) -> int:
    """The router's outputs: the published number of routed experts."""
    return cfg["published"]["n_routed_experts"]


def _is_moe(cfg: dict, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def _layer_leaves(cfg: dict, layer: int) -> dict[str, tuple[int, ...]]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    out = {"ln1_g": (d,), "wq": (d, h * (dn + dr)), "wkv_a": (d, r + dr),
           "kv_norm_g": (r,), "wkv_b": (r, h * (dn + dv)), "wo": (h * dv, d),
           "ln2_g": (d,)}
    if not _is_moe(cfg, layer):
        f = cfg["intermediate_size"]
        out.update(wg=(d, f), wu=(d, f), wd=(f, d))
        return out
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    out.update(router=(d, routed(cfg)), ewg=(e, d, f), ewu=(e, d, f),
               ewd=(e, f, d), swg=(d, fs), swu=(d, fs), swd=(fs, d))
    return out


def leaf_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter, by name: one leaf per tensor of each layer (the held
    experts of a layer stacked in one leaf per matrix)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        for k, s in _layer_leaves(cfg, i).items():
            out[f"layers.{i}.{k}"] = s
    out["final_g"] = (d,)
    out["head"] = (d, v)
    return out


def _kind(name: str) -> str:
    return name.rsplit(".", 1)[-1]


def decays(name: str) -> bool:
    """Weight decay applies to matrices, not to norm gains."""
    return not _kind(name).endswith("_g")


def _init_std(cfg: dict, name: str) -> float | None:
    """Standard deviation of a normal init; None for gains (1).  Output
    projections shrink with depth, GPT-2 style."""
    k, d = _kind(name), cfg["hidden_size"]
    depth = 2 * cfg["num_hidden_layers"]
    if k == "embed":
        return 0.02
    if k in ("wq", "wkv_a", "wg", "wu", "router", "ewg", "ewu", "swg", "swu",
             "head"):
        return 1.0 / math.sqrt(d)
    if k == "wkv_b":
        return 1.0 / math.sqrt(cfg["kv_lora_rank"])
    if k == "wo":
        return 1.0 / math.sqrt(cfg["num_attention_heads"] * cfg["v_head_dim"]
                               * depth)
    width = {"wd": cfg["intermediate_size"],
             "ewd": cfg["moe_intermediate_size"],
             "swd": cfg["n_shared_experts"] * cfg["moe_intermediate_size"]}
    if k in width:
        return 1.0 / math.sqrt(width[k] * depth)
    return None


def draw_flat(cfg: dict, key, name: str, size: int):
    """One leaf's initial values as a flat float32 vector."""
    std = _init_std(cfg, name)
    if std is None:
        return jnp.ones((size,), jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.normal(k, (size,), jnp.float32) * std


def init_params(cfg: dict, key) -> dict[str, jax.Array]:
    return {name: draw_flat(cfg, key, name, math.prod(s)).reshape(s)
            for name, s in leaf_shapes(cfg).items()}


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _rms(cfg, x, g):
    var = jnp.mean(x * x, -1, keepdims=True)
    return x * lax.rsqrt(var + cfg["rms_norm_eps"]) * g


def _yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_frequencies(cfg: dict) -> np.ndarray:
    """YaRN's inverse frequencies of the rope dimensions, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    ys = cfg["rope_scaling"]
    factor, orig = ys["factor"], ys["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(ys["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                     # DeepSeek's inv_freq_mask
    return (extra / factor * (1 - keep) + extra * keep).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    ys = cfg["rope_scaling"]
    m = _yarn_get_mscale(ys["factor"], ys["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(cfg, x):
    """x [b, t, h, dr]: rotate the two halves of each head by position."""
    t, half = x.shape[1], x.shape[-1] // 2
    ys = cfg["rope_scaling"]
    m = (_yarn_get_mscale(ys["factor"], ys["mscale"])
         / _yarn_get_mscale(ys["factor"], ys["mscale_all_dim"]))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * rope_frequencies(cfg)
    cos = (jnp.cos(ang) * m)[None, :, None]
    sin = (jnp.sin(ang) * m)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(num: Numerics, q, k, v, scale: float):
    """Causal softmax attention; q, k [b, t, h, dqk], v [b, t, h, dv]."""
    b, t, h, _ = q.shape
    q = q * scale

    @jax.checkpoint
    def block(qb, lo):
        s = num.einsum("bqhd,bkhd->bhqk", qb, k)
        rows = lo + jnp.arange(qb.shape[1])
        causal = rows[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return num.einsum("bhqk,bkhd->bqhd", p, v)

    n = min(ATTN_BLOCK, t)
    outs = [block(q[:, lo:lo + n], lo) for lo in range(0, t, n)]
    return jnp.concatenate(outs, 1).reshape(b, t, -1)


def _mla(cfg, num: Numerics, p: dict, x):
    b, t, _ = x.shape
    h = cfg["num_attention_heads"]
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    mm = lambda a, w: num.einsum("btd,df->btf", a, w)
    q = mm(x, p["wq"]).reshape(b, t, h, dn + dr)
    kv = mm(x, p["wkv_a"])
    kvb = mm(_rms(cfg, kv[..., :r], p["kv_norm_g"]), p["wkv_b"]).reshape(
        b, t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(cfg, q[..., dn:])], -1)
    k_rope = _rope(cfg, kv[:, :, None, r:])
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_rope, (b, t, h, dr))], -1)
    out = _attention(num, q, k, kvb[..., dn:], softmax_scale(cfg))
    return mm(out, p["wo"])


def _swiglu(num: Numerics, x, wg, wu, wd):
    mm = lambda a, w: num.einsum("btd,df->btf", a, w)
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def _balance(probs, idx, groups: int):
    """The program's balance term, per group of rows, averaged."""
    b, t, e = probs.shape
    probs = probs.reshape(groups, b // groups * t, e)
    first = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32).reshape(
        groups, b // groups * t, e)
    return jnp.mean(e * jnp.sum(jnp.mean(probs, 1) * jnp.mean(first, 1), -1))


def _experts(cfg, num: Numerics, p: dict, x, groups: int):
    """The held experts' part of the layer plus the shared experts, and the
    layer's balance term."""
    probs = jax.nn.softmax(num.einsum("btd,de->bte", x, p["router"]), -1)
    gate, idx = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, -1, keepdims=True)
    gate = gate * cfg["routed_scaling_factor"]
    y = _swiglu(num, x, p["swg"], p["swu"], p["swd"])
    for e in range(cfg["n_routed_experts"]):
        w = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)      # 0: not routed
        y = y + w[..., None] * _swiglu(num, x, p["ewg"][e], p["ewu"][e],
                                       p["ewd"][e])
    return y, _balance(probs, idx, groups)


def _layer(cfg, num: Numerics, p: dict, x, groups: int, moe: bool):
    x = x + _mla(cfg, num, p, _rms(cfg, x, p["ln1_g"]))
    h = _rms(cfg, x, p["ln2_g"])
    if not moe:
        return x + _swiglu(num, h, p["wg"], p["wu"], p["wd"]), jnp.float32(0)
    y, aux = _experts(cfg, num, p, h, groups)
    return x + y, aux


def loss(cfg: dict, params: dict, tokens, targets, mask,
         num: Numerics = FP32, groups: int = 1):
    """Mean token cross-entropy over the rows' unmasked positions, plus the
    balance terms; ``groups``: the devices the rows are split over."""
    x = params["embed"][tokens]
    aux = 0.0
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layers.{i}."
        p = {k[len(pre):]: a for k, a in params.items() if k.startswith(pre)}
        x, a = jax.checkpoint(
            lambda p, x, moe=_is_moe(cfg, i): _layer(cfg, num, p, x, groups,
                                                      moe))(p, x)
        aux = aux + a
    x = _rms(cfg, x, params["final_g"])
    logits = num.einsum("btd,dv->btv", x, params["head"])
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    ce = jnp.sum((lse - tgt) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return ce + cfg["router_aux_weight"] * aux, ce


# ---------------------------------------------------------------------------
# one optimizer step, and the readings
# ---------------------------------------------------------------------------

def train_step(cfg: dict, opt: dict, num: Numerics, params, m, v, step,
               batch, groups: int = 1):
    """One AdamW step over a batch of micro-batches ``[micro, rows, seq]``,
    as ``reference.dense.train_step``: the mean of the micro-batches'
    gradients, clipped to a global norm of ``clip_norm``.  Returns the new
    params, moments, the mean cross-entropy and the clipped gradient."""
    def micro(acc, mb):
        g_acc, l_acc = acc
        (_, ce), g = jax.value_and_grad(
            lambda p: loss(cfg, p, mb["tokens"], mb["targets"], mb["mask"],
                           num, groups), has_aux=True)(params)
        return (jax.tree.map(jnp.add, g_acc, g), l_acc + ce), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (g, lsum), _ = lax.scan(micro, (zeros, jnp.float32(0)), batch)
    s = batch["tokens"].shape[0]
    g = jax.tree.map(lambda a: a / s, g)
    gnorm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
    g = jax.tree.map(
        lambda a: a * jnp.minimum(1.0, opt["clip_norm"]
                                  / jnp.maximum(gnorm, 1e-12)), g)
    lr, t = lr_at(opt, step), jnp.asarray(step, jnp.float32) + 1
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    new_p, new_m, new_v = {}, {}, {}
    for k in params:
        new_m[k] = b1 * m[k] + (1 - b1) * g[k]
        new_v[k] = b2 * v[k] + (1 - b2) * g[k] * g[k]
        upd = (new_m[k] / (1 - b1 ** t)) / (
            jnp.sqrt(new_v[k] / (1 - b2 ** t)) + eps)
        if decays(k):
            upd = upd + wd * params[k]
        new_p[k] = params[k] - lr * upd
    return new_p, new_m, new_v, lsum / s, g


def readings(cfg: dict, opt: dict, key, batches: list[dict],
             num: Numerics = FP32, devices: list | None = None) -> dict:
    """The numbers the comparison reads, from ``len(batches)`` steps, as
    ``reference.dense.readings`` takes them: each step's mean loss, each
    leaf's norm of the first step's clipped gradient, and each leaf's norm
    of its change over the steps.  With several ``devices`` each
    micro-batch's rows are split over them."""
    groups = 1 if devices is None else len(devices)
    step = jax.jit(lambda p, m, v, s, b: train_step(cfg, opt, num, p, m, v,
                                                    s, b, groups),
                   donate_argnums=(0, 1, 2))
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    place = lambda b: jax.tree.map(jnp.asarray, b)
    if groups > 1:
        mesh = Mesh(np.array(devices), ("rows",))
        params = jax.device_put(params, NamedSharding(mesh, P()))
        place = lambda b: jax.device_put(
            b, NamedSharding(mesh, P(None, "rows")))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad = [], None
    for i, b in enumerate(batches):
        params, m, v, l, g = step(params, m, v, jnp.int32(i), place(b))
        losses.append(float(l))
        if grad is None:
            grad = jax.device_get(jax.jit(norms)(g))
        del g
    del m, v
    params = jax.device_put(params, jax.devices()[0] if devices is None
                            else devices[0])
    delta = jax.jit(lambda p, k: norms(jax.tree.map(
        jnp.subtract, p, init_params(cfg, k))))(params, key)
    return {"losses": losses,
            "grad": {k: float(x) for k, x in grad.items()},
            "delta": {k: float(x) for k, x in jax.device_get(delta).items()}}
