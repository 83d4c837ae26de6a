"""Which schedule each layer pool takes (``models.lm.pool_route``).

Training re-gathers in the backward (the remat carry): its step keeps no
stacked ``[stack, flat_len]`` gathered buffer, calls no host callback, and
trains bitwise as the stored carry and the serial schedule do, on one
device and on a repl=2 x shard=2 mesh (tests/carry_harness.py, in a
subprocess).  Serving, enc-dec decoder pools and one-layer pools keep
their own routes.
"""

import dataclasses
import math
import pathlib
import re

import jax.numpy as jnp
import pytest

from harness_util import run_harness, stored_carry
from repro.configs import get_config, smoke_variant
from repro.core.comm import GatherPolicy
from repro.core.mics import (
    MiCSConfig, build_train_step, init_state, init_state_shapes,
    make_batch_shapes,
)
from repro.core.topology import elastic_host_topology
from repro.launch.train import build_training
from repro.models import lm
from repro.models.build import build_model
from repro.optim.adamw import OptConfig
from repro.runtime.serving import build_serve_steps
from tiny_bert import tiny_bert

HARNESS = pathlib.Path(__file__).parent / "carry_harness.py"
NO_PREFETCH = GatherPolicy(prefetch=False)

# case -> (policy, stack, what the pool observes, route)
ROUTES = {
    "train": (GatherPolicy(), 4, {}, "remat"),
    "serve": (GatherPolicy(), 4, {"serving": True}, "stored"),
    "encdec_decoder": (GatherPolicy(), 4, {"enc_out": True}, "stored"),
    "one_layer": (GatherPolicy(), 1, {}, "serial"),
    "one_layer_serve": (GatherPolicy(), 1, {"serving": True}, "serial"),
    "no_prefetch": (NO_PREFETCH, 4, {}, "serial"),
    "no_prefetch_serve": (NO_PREFETCH, 4, {"serving": True}, "serial"),
    "no_prefetch_encdec_decoder": (NO_PREFETCH, 4, {"enc_out": True},
                                   "serial"),
}


@pytest.mark.parametrize("case", sorted(ROUTES))
def test_pool_route(case):
    policy, stack, seen, want = ROUTES[case]
    assert lm.pool_route(stack, policy, **seen) == want


@pytest.mark.parametrize("arch,layers,want", [
    ("bert-10b", 4, {"layers": "remat"}),
    ("yi-9b", 1, {"layers": "serial"}),
    ("whisper-large-v3", None, {"enc": "remat", "dec": "stored"}),
])
def test_train_routes(arch, layers, want):
    cfg = get_config(arch)
    cfg = smoke_variant(cfg) if layers is None \
        else dataclasses.replace(cfg, n_layers=layers)
    model = build_model(cfg, tp=1)
    assert lm.train_routes(model, GatherPolicy()) == want


@pytest.mark.parametrize("arch,layers,route", [
    ("bert-10b", 4, "layers=remat attention: kernel=0 jnp=4"),
    ("yi-9b", 1, "layers=serial attention: kernel=0 jnp=1")])
def test_build_training_prints_routes(capsys, arch, layers, route):
    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    build_training(cfg, elastic_host_topology(1, 1), MiCSConfig(),
                   steps=8, global_batch=8, seq=512, lr=1e-4,
                   checkpoint_dir="unused", checkpoint_every=0)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("memplan:")]
    assert len(line) == 1 and re.fullmatch(
        r"memplan: \d+\.\d{3} GiB predicted per device routes: "
        + re.escape(route), line[0]), line


@pytest.fixture(scope="module")
def tiny():
    return build_model(tiny_bert(), tp=1)


def _lowered_train(model, topo, **carry):
    step = build_train_step(model, topo, MiCSConfig(micro_steps=2, **carry),
                            OptConfig(total_steps=8))
    return step.lower(init_state_shapes(model),
                      make_batch_shapes(model, 8, 64, 2))


def test_default_steps_route(monkeypatch, tiny, topo1):
    """The default train step routes its layer pool to remat, and the
    default serve steps to stored."""
    seen = []
    route = lm.pool_route

    def spy(*args, **kw):
        seen.append(route(*args, **kw))
        return seen[-1]

    monkeypatch.setattr(lm, "pool_route", spy)
    _lowered_train(tiny, topo1)
    assert seen and set(seen) == {"remat"}, seen
    seen.clear()
    prefill, decode = build_serve_steps(tiny, topo1, MiCSConfig(),
                                        cache_len=32)
    params = init_state(tiny, topo1, seed=0)["params"]
    logits, caches = prefill(params, {"tokens": jnp.ones((2, 16), jnp.int32)})
    decode(params, caches, jnp.ones((2, 1), jnp.int32), jnp.int32(16),
           jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.float32),
           jnp.ones((2,), bool))
    assert seen and set(seen) == {"stored"}, seen


@pytest.mark.parametrize("arch", ["tiny-bert", "whisper-large-v3"])
def test_train_routes_match_the_lowered_step(monkeypatch, topo1, arch):
    """The routes ``train_routes`` reports (and ``build_training`` prints)
    are the ones ``_apply_pool`` takes while the step is lowered: enc-dec
    decoder pools included."""
    cfg = tiny_bert() if arch == "tiny-bert" \
        else smoke_variant(get_config(arch))
    model = build_model(cfg, tp=1)
    seen = []
    route = lm.pool_route

    def spy(*args, **kw):
        seen.append(route(*args, **kw))
        return seen[-1]

    monkeypatch.setattr(lm, "pool_route", spy)
    _lowered_train(model, topo1)
    want = list(lm.train_routes(model, GatherPolicy()).values())
    assert seen[:len(want)] == want, (seen, want)
    assert set(seen) == set(want), (seen, want)


def test_default_train_step_keeps_no_stacked_gathered_buffer(tiny, topo1):
    """The lowered default step holds no ``[stack, flat_len]`` buffer of
    the gathered dtype; the stored carry's step does (its residual)."""
    pool = tiny.pool("layers")
    dtype = {"bfloat16": "bf16", "float32": "f32"}[
        jnp.dtype(MiCSConfig().gather_dtype).name]
    buf = f"tensor<{pool.stack}x{pool.layout.flat_len}x{dtype}>"
    with stored_carry():
        stored = _lowered_train(tiny, topo1).as_text()
    assert buf in stored
    assert buf not in _lowered_train(tiny, topo1).as_text()


@pytest.fixture(scope="module")
def trained():
    return run_harness(HARNESS)


@pytest.mark.parametrize("mesh", ["one_device", "repl2_shard2"])
def test_carries_train_alike(trained, mesh):
    runs = trained[mesh]
    assert all(math.isfinite(v) for row in runs["default"]["steps"]
               for v in row), runs["default"]
    for carry in ("serial", "stored"):
        assert runs[carry] == runs["default"], (carry, runs)


@pytest.mark.parametrize("case", [
    "tiny_bert_one_device", "tiny_bert_repl2_shard2",
    "dsv2lite_smoke_one_device"])
def test_train_step_has_no_host_callback(trained, case):
    """The compiled default train step calls nothing on the host: a host
    callback would stall the device every step."""
    assert trained["host_callbacks"][case] == [], trained["host_callbacks"]

