"""The prefetch carries train alike, on one device and on a repl=2 x
shard=2 mesh of four virtual CPU devices (in a subprocess, so the main
pytest process keeps one device).

For each mesh, the tiny bert trains three steps from the same state under
the serial schedule, the stored carry (``harness_util.stored_carry``) and
the default ``MiCSConfig`` (the remat carry); the script prints one JSON
object with, per mesh and carry, each step's loss and gradient norm and a
digest of the final parameters.  Under ``host_callbacks`` it adds, for the
default train step of the tiny bert on each mesh and of DeepSeek-V2-Lite's
smoke variant on one device, the host callbacks its compiled HLO calls.
tests/test_carry_route.py asserts on it.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", "")
)

import contextlib  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from harness_util import stored_carry  # noqa: E402
from repro.configs import get_config, smoke_variant  # noqa: E402
from repro.core.mics import (  # noqa: E402
    MiCSConfig, build_train_step, init_state, init_state_shapes,
    make_batch_shapes,
)
from repro.core.topology import MiCSTopology, make_host_mesh  # noqa: E402
from repro.models.build import build_model  # noqa: E402
from repro.optim.adamw import OptConfig  # noqa: E402
from tiny_bert import tiny_bert  # noqa: E402

MESHES = {"one_device": (1, 1), "repl2_shard2": (2, 2)}
CARRIES = {
    "serial": dict(prefetch=False),
    "stored": dict(),
    "default": dict(),
}
MICRO, SEQ = 2, 32
# a custom call into the host: on the CPU ``xla_ffi_python_cpu_callback``
# (io_callback, pure_callback, debug.callback), ``..._gpu_callback`` on GPUs
CALLBACK = re.compile(r'custom_call_target="[^"]*callback[^"]*"')


def trajectory(repl: int, shard: int, carry_kw: dict, steps: int = 3):
    cfg = tiny_bert()
    model = build_model(cfg, tp=1)
    topo = MiCSTopology(make_host_mesh(1, repl, shard, 1))
    step = build_train_step(
        model, topo, MiCSConfig(micro_steps=MICRO, **carry_kw),
        OptConfig(total_steps=8, warmup_steps=1, lr_max=3e-3))
    rng = np.random.default_rng(5)
    b = 4 * repl * shard
    tokens = rng.integers(0, cfg.vocab, (MICRO, b, SEQ + 1))
    batch = {"tokens": jnp.asarray(tokens[..., :-1], jnp.int32),
             "targets": jnp.asarray(tokens[..., 1:], jnp.int32),
             "mask": jnp.ones((MICRO, b, SEQ), jnp.float32)}
    state = init_state(model, topo, seed=11)
    rows = []
    for _ in range(steps):
        state, m = step(state, batch)
        rows.append([float(m["loss"]), float(m["grad_norm"])])
    digest = hashlib.sha256()
    for leaf in jax.tree.leaves(state["params"]):
        digest.update(np.asarray(leaf).tobytes())
    return {"steps": rows, "params": digest.hexdigest()}


def host_callbacks(cfg, repl: int, shard: int) -> list[str]:
    """The host callbacks of the compiled default train step."""
    model = build_model(cfg, tp=1)
    topo = MiCSTopology(make_host_mesh(1, repl, shard, 1))
    step = build_train_step(model, topo, MiCSConfig(micro_steps=MICRO),
                            OptConfig(total_steps=8))
    hlo = step.lower(
        init_state_shapes(model),
        make_batch_shapes(model, 4 * repl * shard, SEQ, MICRO),
    ).compile().as_text()
    assert "ENTRY" in hlo, hlo[:200]
    return CALLBACK.findall(hlo)


def run(carry: str, dims: tuple, kw: dict):
    with stored_carry() if carry == "stored" else contextlib.nullcontext():
        return trajectory(*dims, kw)


out = {mesh: {carry: run(carry, dims, kw) for carry, kw in CARRIES.items()}
       for mesh, dims in MESHES.items()}
out["host_callbacks"] = {
    **{f"tiny_bert_{mesh}": host_callbacks(tiny_bert(), *dims)
       for mesh, dims in MESHES.items()},
    "dsv2lite_smoke_one_device": host_callbacks(
        smoke_variant(get_config("deepseek-v2-lite")), 1, 1),
}
print(json.dumps(out))
