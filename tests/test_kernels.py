"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(every call passes interpret=True, which executes the Pallas kernel body
on CPU; the kernels default to compiling for the TPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (
    attention_ref, causal_attention, flash_attention, flash_attention_gqa)
from repro.kernels.rglru import rglru_ref, rglru_scan
from repro.kernels.rmsnorm import rmsnorm_nd, rmsnorm_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bh,t,d", [(2, 128, 32), (4, 256, 64), (1, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention(bh, t, d, dtype, causal, window):
    q = jnp.asarray(RNG.normal(size=(bh, t, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(bh, t, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(bh, t, d)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("g,hkv", [(2, 2), (4, 1), (1, 4)])
def test_flash_attention_gqa_layout(g, hkv):
    b, t, dh = 2, 128, 32
    q = jnp.asarray(RNG.normal(size=(b, t, hkv, g, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, t, hkv, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, t, hkv, dh)), jnp.float32)
    got = flash_attention_gqa(q, k, v, block_q=64, block_k=64,
                              interpret=True)
    # oracle via the model-layer attention (same [b,t,hkv,g,dh] layout)
    from repro.models.layers import attention
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               rtol=3e-5, atol=3e-5)


# (hkv, g, dh, dv, scale) at sequence 256, two 128-token tiles of the
# fused kernel: GQA, MHA at head 64, and MLA's qk 192 / v 128 with a scale
# of its own
CAUSAL_CASES = {
    "gqa": (2, 4, 128, 128, None),
    "mha_dh64": (4, 1, 64, 64, None),
    "mla": (2, 1, 192, 128, 0.0625),
}


@pytest.mark.parametrize("case", list(CAUSAL_CASES))
def test_causal_attention_matches_jnp(case):
    """The fused causal kernel's output, and its gradients w.r.t. q, k and
    v, against ``layers.attention``'s jnp path (the path on the CPU)."""
    from repro.models.layers import attention

    hkv, g, dh, dv, scale = CAUSAL_CASES[case]
    b, t = 1, 256
    q = jnp.asarray(RNG.normal(size=(b, t, hkv, g, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, t, hkv, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, t, hkv, dv)), jnp.float32)
    # a cotangent that differs by position, head and channel
    w = jnp.asarray(RNG.normal(size=(b, t, hkv, g, dv)), jnp.float32)

    def kernel(q, k, v):
        return causal_attention(q, k, v, scale=scale, interpret=True)

    def jnp_path(q, k, v):
        return attention(q, k, v, scale=scale, causal=True)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(
            q, k, v)

    tol = dict(rtol=1e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(jnp_path(q, k, v)), **tol)
    for name, got, want in zip("qkv", grads(kernel), grads(jnp_path)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   err_msg=f"d{name}", **tol)


# (tq, tk, attention_path keywords besides the platform, expected path)
PATH_CASES = {
    "train_bert_s512": (512, 512, {}, "kernel"),
    "train_yi_s4096": (4096, 4096, {}, "kernel"),
    "train_mla_s4096": (4096, 4096, {}, "kernel"),
    "prefill_s256": (256, 256, {}, "kernel"),
    "decode_kv_valid_len": (1, 4096, dict(causal=False, kv_valid_len=7), "jnp"),
    "chunk_kv_valid_len": (128, 128, dict(kv_valid_len=100), "jnp"),
    "window": (4096, 4096, dict(window=2048), "jnp"),
    "q_offset": (512, 512, dict(q_offset=512), "jnp"),
    "k_offset": (512, 512, dict(k_offset=128), "jnp"),
    "traced_offset": (512, 512, dict(q_offset=jnp.int32(0)), "jnp"),
    "not_tile_multiple": (200, 200, {}, "jnp"),
    "cross": (512, 1024, dict(causal=False), "jnp"),
    "not_causal": (512, 512, dict(causal=False), "jnp"),
    "cpu": (4096, 4096, dict(platform="cpu"), "jnp"),
}


@pytest.mark.parametrize("case", list(PATH_CASES))
def test_attention_path(case):
    """Which calls ``layers.attention`` gives the fused kernel on the TPU."""
    from repro.models.layers import attention_path

    tq, tk, kw, want = PATH_CASES[case]
    kw = dict(dict(causal=True, window=0, q_offset=0, k_offset=0,
                   kv_valid_len=None, platform="tpu"), **kw)
    assert attention_path(tq, tk, **kw) == want


@pytest.mark.parametrize("arch,layers,seq,want", [
    ("bert-10b", 4, 512, {"kernel": 4}),
    ("yi-9b", 1, 4096, {"kernel": 1}),
    ("deepseek-v2-lite", 5, 4096, {"kernel": 5}),
    ("recurrentgemma-2b", 3, 4096, {"jnp": 1}),
])
def test_attention_paths_of_train_steps(monkeypatch, arch, layers, seq, want):
    """``lm.attention_paths`` (``build_training``'s ``attention:`` count)
    with the backend reading as the TPU: every attention layer of the
    benchmark's three configurations takes the kernel; a windowed one
    (recurrentgemma's local attention, its one in three layers) does not."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.build import build_model

    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dict(lm.attention_paths(build_model(cfg, tp=1), seq)) == want


@pytest.mark.parametrize("shape", [(64, 128), (4, 16, 256), (2, 8, 8, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jnp.asarray(RNG.normal(size=shape), dtype)
    s = jnp.asarray(RNG.normal(size=shape[-1]) * 0.2, jnp.float32)
    got = rmsnorm_nd(x, s, interpret=True)
    want = rmsnorm_ref(x, s)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("b,t,c", [(2, 128, 128), (1, 512, 256), (3, 96, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru(b, t, c, dtype):
    a = jnp.asarray(RNG.uniform(0.7, 0.999, size=(b, t, c)), dtype)
    bb = jnp.asarray(RNG.normal(size=(b, t, c)) * 0.1, dtype)
    got = rglru_scan(a, bb, interpret=True)
    want = rglru_ref(a, bb)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=5e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_rglru_multi_block_carry():
    """Several time blocks, each walked in several slabs: the state carried
    across slabs and across the sequential time-grid axis matches the
    oracle."""
    a = jnp.asarray(RNG.uniform(0.7, 0.999, size=(2, 256, 256)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(2, 256, 256)) * 0.1, jnp.float32)
    got = rglru_scan(a, b, block_t=64, block_c=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(rglru_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


def test_rglru_ref_matches_sequential_loop():
    """The associative-scan oracle equals the plain sequential recurrence."""
    a = np.asarray(RNG.uniform(0.8, 0.99, size=(2, 64, 32)), np.float32)
    b = np.asarray(RNG.normal(size=(2, 64, 32)), np.float32)
    h = np.zeros((2, 32), np.float32)
    seq = np.empty_like(a)
    for t in range(a.shape[1]):
        h = a[:, t] * h + b[:, t]
        seq[:, t] = h
    # associative scan reorders the products -> fp32 rounding differences
    np.testing.assert_allclose(
        np.asarray(rglru_ref(jnp.asarray(a), jnp.asarray(b))), seq,
        rtol=1e-4, atol=1e-6)
