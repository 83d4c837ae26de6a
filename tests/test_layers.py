"""Unit tests of the numeric layers: chunked attention == direct softmax,
local windows, GQA grouping, vocab-parallel CE == plain CE, rotary, MoE
dispatch == dense-expert reference."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L

RNG = np.random.default_rng(3)


def _qkv(b=2, t=256, hkv=2, g=2, dh=32, dtype=jnp.float32):
    q = jnp.asarray(RNG.normal(size=(b, t, hkv, g, dh)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, t, hkv, dh)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, t, hkv, dh)), dtype)
    return q, k, v


def _direct(q, k, v, causal=True, window=0):
    """Dense per-head reference."""
    b, t, hkv, g, dh = q.shape
    s = np.einsum("bqhgd,bkhd->bhgqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) / np.sqrt(dh)
    qp = np.arange(t)[:, None]
    kp = np.arange(t)[None, :]
    mask = np.ones((t, t), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= (qp - kp) < window
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("bhgqk,bkhd->bqhgd", p, np.asarray(v, np.float64))
    return out


@pytest.mark.parametrize("window", [0, 64])
def test_chunked_attention_matches_direct(window):
    q, k, v = _qkv()
    got = L.attention(q, k, v, causal=True, window=window, chunk_q=64)
    want = _direct(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_attention_decode_path_matches_prefix():
    """Decode (q_len=1, kv_valid_len) == last row of the full computation."""
    q, k, v = _qkv(t=64)
    full = L.attention(q, k, v, causal=True)
    last = L.attention(q[:, -1:], k, v, causal=False, kv_valid_len=64)
    np.testing.assert_allclose(
        np.asarray(last)[:, 0], np.asarray(full)[:, -1], rtol=2e-5, atol=2e-5)


def test_tp_cross_entropy_matches_dense(topo1):
    """tp=1 vocab-parallel CE == plain logsumexp CE, incl. vocab padding."""
    b, t, v_real, v_pad = 2, 8, 50, 64
    logits = jnp.asarray(RNG.normal(size=(b, t, v_pad)), jnp.float32)
    targets = jnp.asarray(RNG.integers(0, v_real, (b, t)), jnp.int32)
    mask = jnp.ones((b, t), jnp.float32)
    ctx = L.Ctx(tp=1)
    got = L.tp_cross_entropy(logits, targets, mask,
                             vocab_real=v_real, vocab_padded=v_pad, ctx=ctx)
    lg = np.asarray(logits)[:, :, :v_real]
    lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
        + lg.max(-1)
    nll = lse - np.take_along_axis(lg, np.asarray(targets)[..., None],
                                   -1)[..., 0]
    np.testing.assert_allclose(float(got), nll.mean(), rtol=1e-5)


def test_rotary_preserves_norm_and_relative_phase():
    x = jnp.asarray(RNG.normal(size=(1, 16, 2, 32)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16), (1, 16))
    y = L.rotary(x, pos, theta=10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # relative property: <R(p)q, R(k)k'> depends only on p-k
    q = jnp.asarray(RNG.normal(size=(1, 1, 1, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 1, 1, 32)), jnp.float32)

    def score(pq, pk):
        rq = L.rotary(q, jnp.full((1, 1), pq), 10_000.0)
        rk = L.rotary(k, jnp.full((1, 1), pk), 10_000.0)
        return float(jnp.sum(rq * rk))

    np.testing.assert_allclose(score(5, 3), score(12, 10), rtol=1e-4)


@pytest.mark.parametrize("norm_topk_prob", [True, False])
@pytest.mark.parametrize("held", [8, 4])
def test_moe_dispatch_matches_dense_reference(topo1, norm_topk_prob, held):
    """The dropless grouped expert layer == an explicit per-token mix of the
    experts it holds (the first ``held`` of 8), top-k over all 8, with the
    top-k gates renormalised or not."""
    import dataclasses

    from repro.configs import get_config, smoke_variant
    from repro.models.blocks import moe_routed

    cfg = smoke_variant(get_config("deepseek-moe-16b"))
    cfg = dataclasses.replace(cfg, experts_held=held,
                              norm_topk_prob=norm_topk_prob)
    d, e, k = 16, cfg.n_experts, cfg.top_k
    n = 32
    t = {
        "router.w": jnp.asarray(RNG.normal(size=(d, e)) * 0.3, jnp.float32),
        "moe.wg": jnp.asarray(RNG.normal(size=(held, d, 8)) * 0.3, jnp.float32),
        "moe.wu": jnp.asarray(RNG.normal(size=(held, d, 8)) * 0.3, jnp.float32),
        "moe.wd": jnp.asarray(RNG.normal(size=(held, 8, d)) * 0.3, jnp.float32),
    }
    x = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    ctx = L.Ctx(tp=1)
    got, aux = moe_routed(x, t, cfg, ctx)

    # dense reference over the held experts
    logits = np.asarray(x, np.float64) @ np.asarray(t["router.w"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    topk = np.argsort(-probs, axis=-1)[:, :k]
    want = np.zeros((n, d), np.float32)
    load = np.zeros(held)
    for i in range(n):
        gates = probs[i, topk[i]]
        if norm_topk_prob:
            gates = gates / gates.sum()
        for j, eid in enumerate(topk[i]):
            if eid >= held:
                continue
            load[eid] += 1
            h = np.asarray(x)[i] @ np.asarray(t["moe.wg"])[eid]
            u = np.asarray(x)[i] @ np.asarray(t["moe.wu"])[eid]
            act = h / (1 + np.exp(-h)) * u
            want[i] += gates[j] * (act @ np.asarray(t["moe.wd"])[eid])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux[1]), load.max() / load.mean(),
                               rtol=1e-6)


def test_moe_rows_past_the_groups_do_not_leak(topo1, monkeypatch):
    """A grouped product may leave the rows past its groups undefined, in
    its transposes too (the TPU's does): with NaN there, the expert
    layer's output and gradients are those of the CPU's zeros."""
    import dataclasses

    import jax
    from jax import lax

    from repro.configs import get_config, smoke_variant
    from repro.models.blocks import moe_routed

    cfg = dataclasses.replace(smoke_variant(get_config("deepseek-moe-16b")),
                              experts_held=4)
    d, held = 16, 4
    t = {
        "router.w": jnp.asarray(RNG.normal(size=(d, 8)) * 0.3, jnp.float32),
        "moe.wg": jnp.asarray(RNG.normal(size=(held, d, 8)) * 0.3, jnp.float32),
        "moe.wu": jnp.asarray(RNG.normal(size=(held, d, 8)) * 0.3, jnp.float32),
        "moe.wd": jnp.asarray(RNG.normal(size=(held, 8, d)) * 0.3, jnp.float32),
    }
    x = jnp.asarray(RNG.normal(size=(32, d)), jnp.float32)

    def loss(t, x):
        y, aux = moe_routed(x, t, cfg, L.Ctx(tp=1))
        return jnp.sum(y * y) + aux[0]

    want = jax.value_and_grad(loss, argnums=(0, 1))(t, x)
    real = lax.ragged_dot

    def undefined_past_groups(lhs, rhs, group_sizes, **kw):
        def fill(a):
            past = jnp.arange(a.shape[0]) >= jnp.sum(group_sizes)
            return jnp.where(past[:, None], jnp.nan, a)

        @jax.custom_vjp
        def f(l, r):
            return fill(real(l, r, group_sizes, **kw))

        def fwd(l, r):
            return f(l, r), (l, r)

        def bwd(res, ct):
            dl, dr = jax.vjp(lambda a, b: real(a, b, group_sizes, **kw),
                             *res)[1](ct)
            return fill(dl), dr

        f.defvjp(fwd, bwd)
        return f(lhs, rhs)

    monkeypatch.setattr(lax, "ragged_dot", undefined_past_groups)
    got = jax.value_and_grad(loss, argnums=(0, 1))(t, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)
