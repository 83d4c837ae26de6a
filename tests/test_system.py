"""End-to-end behaviour: every assigned architecture trains (reduced config)
on CPU — one forward/backward/optimizer step with finite loss and the exact
state structure, plus a serve (prefill+decode) smoke for each family."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config, smoke_variant
from repro.core.mics import MiCSConfig, build_train_step, init_state
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.runtime.serving import build_serve_steps

ARCH_NAMES = [c.name for c in ASSIGNED]


def _batch(cfg, s=2, b=2, t=32, seed=0):
    rng = np.random.default_rng(seed)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (s, b, t)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab, (s, b, t)), jnp.int32),
        "mask": jnp.ones((s, b, t), jnp.float32),
    }
    if cfg.family == "vlm":
        batch["vision"] = jnp.asarray(
            rng.normal(size=(s, b, cfg.n_vision_tokens, cfg.d_model)),
            jnp.bfloat16)
    if cfg.family == "encdec":
        batch["audio"] = jnp.asarray(
            rng.normal(size=(s, b, cfg.n_audio_frames, cfg.d_model)),
            jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_arch_smoke_train_step(arch, topo1):
    cfg = smoke_variant(get_config(arch))
    model = build_model(cfg, tp=1)
    state = init_state(model, topo1)
    step = build_train_step(
        model, topo1, MiCSConfig(micro_steps=2),
        OptConfig(total_steps=10, warmup_steps=0, lr_max=1e-3))
    batch = _batch(cfg)

    before = {k: np.asarray(v) for k, v in state["params"].items()}
    state2, metrics = step(state, batch)

    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0, loss
    assert np.isfinite(float(metrics["grad_norm"]))
    # params moved, structure/shape preserved, no NaNs
    for name, arr in state2["params"].items():
        a = np.asarray(arr)
        assert a.shape == before[name].shape
        assert np.all(np.isfinite(a)), name
        assert not np.array_equal(a, before[name]), f"{name} did not update"
    assert int(np.asarray(state2["step"])) == 1


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_arch_smoke_decode(arch, topo1):
    cfg = smoke_variant(get_config(arch))
    model = build_model(cfg, tp=1)
    state = init_state(model, topo1, seed=1)
    prefill_fn, decode_fn = build_serve_steps(
        model, topo1, MiCSConfig(), cache_len=24)
    rng = np.random.default_rng(2)
    b, t0 = 2, 16
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, t0)),
                                   jnp.int32)}
    if cfg.family == "vlm":
        batch["vision"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_vision_tokens, cfg.d_model)), jnp.bfloat16)
    if cfg.family == "encdec":
        batch["audio"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_audio_frames, cfg.d_model)), jnp.bfloat16)

    logits, caches = prefill_fn(state["params"], batch)
    assert logits.shape[:2] == (b, 1)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    tok = jnp.argmax(jnp.asarray(logits[:, -1:]), axis=-1).astype(jnp.int32)
    for i in range(3):
        logits, tok, caches = decode_fn(
            state["params"], caches, tok.astype(jnp.int32), jnp.int32(t0 + i))
        arr = np.asarray(logits, np.float32)
        assert arr.shape[:2] == (b, 1)
        assert np.all(np.isfinite(arr))
        ids = np.asarray(tok)
        assert ids.min() >= 0 and ids.max() < cfg.vocab


def test_deepseek_v2_lite_smoke_trains_through_the_launcher(tmp_path, capsys):
    """``python -m repro.launch.train --arch deepseek-v2-lite --smoke``'s
    path: the smoke variant keeps a leading dense layer, MLA and a held share
    of the experts; ``build_training`` prints a dense pool and then the
    expert pool on the remat route; the loop trains it."""
    from repro.core.topology import elastic_host_topology
    from repro.launch.train import build_training

    cfg = smoke_variant(get_config("deepseek-v2-lite"))
    assert cfg.first_dense_layers == 1 and cfg.kv_lora_rank
    assert 0 < cfg.experts_held < cfg.n_experts
    run = build_training(cfg, elastic_host_topology(1, 1),
                         MiCSConfig(micro_steps=2), steps=4, global_batch=4,
                         seq=32, lr=1e-3, checkpoint_dir=str(tmp_path),
                         checkpoint_every=0)
    assert "routes: dense=serial layers=remat" in capsys.readouterr().out
    stats = run.train()
    assert len(stats.losses) == 4
    assert np.all(np.isfinite(stats.losses))


def test_mla_serving_raises_not_implemented(topo1):
    """MLA trains only: the serve path has no latent cache, and says so."""
    cfg = smoke_variant(get_config("deepseek-v2-lite"))
    model = build_model(cfg, tp=1)
    with pytest.raises(NotImplementedError, match="latent cache"):
        build_serve_steps(model, topo1, MiCSConfig(), cache_len=24)
