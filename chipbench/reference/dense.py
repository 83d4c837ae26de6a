"""Plain dense decoder language model in float32, for training cells.

Forward pass, token cross-entropy, gradients and AdamW, written from the
configuration file's numbers alone: it imports nothing of the program and
takes nothing the program made.  It builds its own weights from the seed
(``init_params``), and the harness hands the same draws to the program.

Both of the benchmark's dense configurations run through it:

* bert-10b: LayerNorm (eps 1e-5) with gain and bias, multi-head
  attention with no position encoding (the configuration is a causal
  decoder with none), a GELU MLP (tanh form) with biases;
* yi-9b: RMSNorm (eps 1e-6), grouped-query attention with rotary
  positions (halves rotated, as in Llama), a SwiGLU MLP without biases.

Every matrix product goes through a ``Numerics``: ``FP32`` computes at
``Precision.HIGHEST`` (on a TPU a float32 product otherwise runs in
bfloat16), and ``FP8`` is the control, one precision step below the
program's bfloat16.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# query rows per attention block: bounds the [heads, rows, seq] scores
ATTN_BLOCK = 1024


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Numerics:
    """How matrix products are computed."""

    name: str
    quantize: Callable | None = None   # fake-quantizes a product's operand

    def einsum(self, spec: str, a, b):
        if self.quantize is not None:
            return _q_einsum(spec, self.quantize, a, b)
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


def _fp8(x, dtype=jnp.float8_e4m3fn):
    """Per-tensor scaled round trip through an 8-bit float."""
    x = x.astype(jnp.float32)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _q_einsum(spec, quantize, a, b):
    """An einsum whose operands are fake-quantized in the forward pass and
    whose incoming gradient is quantized (to e5m2) in the backward pass, as
    in 8-bit float training."""
    ins, out = spec.split("->")
    sa, sb = ins.split(",")

    def mm(x, y):
        return jnp.einsum(spec, x, y, precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    @jax.custom_vjp
    def f(x, y):
        return mm(quantize(x), quantize(y))

    def fwd(x, y):
        xq, yq = quantize(x), quantize(y)
        return mm(xq, yq), (xq, yq)

    def bwd(res, g):
        xq, yq = res
        gq = _fp8(g, jnp.float8_e5m2)
        dx = jnp.einsum(f"{out},{sb}->{sa}", gq, yq,
                        precision=lax.Precision.HIGHEST)
        dy = jnp.einsum(f"{sa},{out}->{sb}", xq, gq,
                        precision=lax.Precision.HIGHEST)
        return dx, dy

    f.defvjp(fwd, bwd)
    return f(a, b)


FP32 = Numerics("fp32")
FP8 = Numerics("fp8", quantize=_fp8)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _layer_leaves(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, f, dh = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    hq, hkv = cfg["n_heads"], cfg["n_kv_heads"]
    ln = cfg["norm"] == "ln"
    out = {"ln1_g": (d,)}
    if ln:
        out["ln1_b"] = (d,)
    out.update(wq=(d, hq * dh), wk=(d, hkv * dh), wv=(d, hkv * dh),
               wo=(hq * dh, d), ln2_g=(d,))
    if ln:
        out["ln2_b"] = (d,)
    if cfg["mlp"] == "gelu":
        out.update(w1=(d, f), b1=(f,), w2=(f, d), b2=(d,))
    else:
        out.update(wg=(d, f), wu=(d, f), wd=(f, d))
    return out


def leaf_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter, by name: one leaf per tensor of each layer."""
    d, v = cfg["d_model"], cfg["vocab"]
    out = {"embed": (v, d)}
    for i in range(cfg["n_layers"]):
        for k, s in _layer_leaves(cfg).items():
            out[f"layers.{i}.{k}"] = s
    out["final_g"] = (d,)
    if cfg["norm"] == "ln":
        out["final_b"] = (d,)
    out["head"] = (d, v)
    return out


def _kind(name: str) -> str:
    return name.rsplit(".", 1)[-1]


def decays(name: str) -> bool:
    """Weight decay applies to matrices, not to norm gains or biases."""
    return _kind(name) not in ("ln1_g", "ln1_b", "ln2_g", "ln2_b", "b1",
                               "b2", "final_g", "final_b")


def _init_std(cfg: dict, name: str) -> float | None:
    """Standard deviation of a normal init; None for gains (1) and biases
    (0).  Output projections shrink with depth, GPT-2 style."""
    k, d, L = _kind(name), cfg["d_model"], cfg["n_layers"]
    if k == "embed":
        return 0.02
    if k in ("wq", "wk", "wv", "w1", "wg", "wu", "head"):
        return 1.0 / math.sqrt(d)
    if k == "wo":
        return 1.0 / math.sqrt(cfg["n_heads"] * cfg["head_dim"] * 2 * L)
    if k in ("w2", "wd"):
        return 1.0 / math.sqrt(cfg["d_ff"] * 2 * L)
    return None


def draw_flat(cfg: dict, key, name: str, size: int):
    """One leaf's initial values as a flat float32 vector."""
    std = _init_std(cfg, name)
    if std is not None:
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return jax.random.normal(k, (size,), jnp.float32) * std
    fill = 1.0 if _kind(name).endswith("_g") else 0.0
    return jnp.full((size,), fill, jnp.float32)


def init_params(cfg: dict, key) -> dict[str, jax.Array]:
    return {name: draw_flat(cfg, key, name, math.prod(s)).reshape(s)
            for name, s in leaf_shapes(cfg).items()}


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _norm(cfg, x, g, b):
    if cfg["norm"] == "ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + 1e-5) * g + b
    var = jnp.mean(x * x, -1, keepdims=True)
    return x * lax.rsqrt(var + 1e-6) * g


def _rope(x, theta):
    """x [b, t, h, dh]: rotate the two halves of each head by position."""
    t, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg, num: Numerics, q, k, v):
    """Causal softmax attention; q [b, t, hq, dh], k/v [b, t, hkv, dh].
    Query head h reads key/value head h // (hq / hkv)."""
    b, t, hq, dh = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, t, hkv, hq // hkv, dh) / math.sqrt(dh)

    @jax.checkpoint
    def block(qb, lo):
        s = num.einsum("bqhgd,bkhd->bhgqk", qb, k)
        rows = lo + jnp.arange(qb.shape[1])
        causal = rows[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return num.einsum("bhgqk,bkhd->bqhgd", p, v)

    n = min(ATTN_BLOCK, t)
    outs = [block(q[:, lo:lo + n], lo) for lo in range(0, t, n)]
    return jnp.concatenate(outs, 1).reshape(b, t, hq * dh)


def _layer(cfg, num: Numerics, p: dict, x):
    b, t, _ = x.shape
    dh, hq, hkv = cfg["head_dim"], cfg["n_heads"], cfg["n_kv_heads"]
    mm = lambda a, w: num.einsum("btd,df->btf", a, w)
    h = _norm(cfg, x, p["ln1_g"], p.get("ln1_b"))
    q = mm(h, p["wq"]).reshape(b, t, hq, dh)
    k = mm(h, p["wk"]).reshape(b, t, hkv, dh)
    v = mm(h, p["wv"]).reshape(b, t, hkv, dh)
    if cfg["use_rope"]:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    x = x + mm(_attention(cfg, num, q, k, v), p["wo"])
    h = _norm(cfg, x, p["ln2_g"], p.get("ln2_b"))
    if cfg["mlp"] == "gelu":
        u = jax.nn.gelu(mm(h, p["w1"]) + p["b1"], approximate=True)
        return x + mm(u, p["w2"]) + p["b2"]
    u = jax.nn.silu(mm(h, p["wg"])) * mm(h, p["wu"])
    return x + mm(u, p["wd"])


def loss(cfg: dict, params: dict, tokens, targets, mask,
         num: Numerics = FP32):
    """Mean token cross-entropy over the rows' unmasked positions."""
    x = params["embed"][tokens]
    for i in range(cfg["n_layers"]):
        pre = f"layers.{i}."
        p = {k[len(pre):]: a for k, a in params.items() if k.startswith(pre)}
        x = jax.checkpoint(lambda p, x: _layer(cfg, num, p, x))(p, x)
    x = _norm(cfg, x, params["final_g"], params.get("final_b"))
    logits = num.einsum("btd,dv->btv", x, params["head"])
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum((lse - tgt) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# one optimizer step
# ---------------------------------------------------------------------------

def lr_at(opt: dict, step):
    """Linear warm-up from 0 over ``warmup_steps``, then cosine to
    ``lr_min_ratio`` of the peak at ``total_steps``."""
    step = jnp.asarray(step, jnp.float32)
    w, n = opt["warmup_steps"], opt["total_steps"]
    frac = jnp.clip((step - w) / max(n - w, 1), 0.0, 1.0)
    cos = opt["lr_min_ratio"] + (1 - opt["lr_min_ratio"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * frac))
    return opt["lr"] * jnp.where(step < w, step / max(w, 1), cos)


def train_step(cfg: dict, opt: dict, num: Numerics, params, m, v, step,
               batch):
    """One AdamW step over a batch of micro-batches ``[micro, rows, seq]``.

    The gradient is the mean of the micro-batches' mean-loss gradients,
    clipped to a global norm of ``clip_norm``.  Returns the new params,
    moments, the mean loss and the clipped gradient.
    """
    def micro(acc, mb):
        g_acc, l_acc = acc
        val, g = jax.value_and_grad(
            lambda p: loss(cfg, p, mb["tokens"], mb["targets"], mb["mask"],
                           num))(params)
        return (jax.tree.map(jnp.add, g_acc, g), l_acc + val), None

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


def norms(tree: dict) -> dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for k, a in tree.items()}


def readings(cfg: dict, opt: dict, key, batches: list[dict],
             num: Numerics = FP32, devices: list | None = None) -> dict:
    """The numbers the comparison reads, from ``len(batches)`` steps.

    ``losses``: each step's mean loss; ``grad``: each leaf's norm of the
    first step's clipped gradient; ``delta``: each leaf's norm of its change
    over all the steps.  With several ``devices`` each micro-batch's rows
    are split over them and the weights and moments copied to each; the
    weights are drawn, and their change taken, on the first.
    """
    step = jax.jit(lambda p, m, v, s, b: train_step(cfg, opt, num, p, m, v,
                                                    s, b),
                   donate_argnums=(0, 1, 2))
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    place = lambda b: jax.tree.map(jnp.asarray, b)
    if devices is not None and len(devices) > 1:
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
