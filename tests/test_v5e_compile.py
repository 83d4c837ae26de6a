"""Compile the main path for a described TPU v5e, no chip attached.

The TPU compiler runs here against a ``v5e:2x2`` topology description, so
what the chip's compiler would refuse — a Pallas lowering it lacks, tiling,
VMEM, a program that does not fit HBM — fails in this file at no chip time.
Nothing runs: these tests say nothing about results or speed.

The topology is described inside a module fixture (never at import, never
in conftest.py): only one process may load libtpu, and under several test
workers only the worker running this file does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    AxisType, Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

HBM_BYTES = 16 * 2**30   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        from jax.experimental import topologies

        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _grad(fn):
    """The gradient w.r.t. every argument of ``sum(fn(...))``."""
    def loss(*args):
        return jnp.sum(fn(*args).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


def _kernel_cases():
    from repro.kernels.flash_attention import causal_attention, flash_attention
    from repro.kernels.rglru import rglru
    from repro.kernels.rmsnorm import rmsnorm

    bf16 = jnp.bfloat16

    def mla(q, k, v):
        return causal_attention(q, k, v, scale=0.1)

    # yi-9b: 32 query heads over 4 kv heads of 128, 4096 tokens
    yi = [((1, 4096, 4, 8, 128), bf16), ((1, 4096, 4, 128), bf16),
          ((1, 4096, 4, 128), bf16)]
    # DeepSeek-V2-Lite's MLA: 16 heads, qk 192, v 128, 4096 tokens
    mla_shapes = [((1, 4096, 16, 1, 192), bf16), ((1, 4096, 16, 192), bf16),
                  ((1, 4096, 16, 128), bf16)]
    return {
        # 8192 token rows at bert-10b's d_model 2560
        "rmsnorm": (rmsnorm, [((8192, 2560), bf16), ((2560,), jnp.float32)]),
        # bert-10b attention: 40 heads of 64 over max_seq 512
        "flash_attention": (flash_attention, [((40, 512, 64), bf16)] * 3),
        # recurrentgemma-2b: lru_width 2560 over 4096 steps
        "rglru": (rglru, [((2, 4096, 2560), bf16)] * 2),
        "causal_attention_yi": (causal_attention, yi),
        "causal_attention_yi_grad": (_grad(causal_attention), yi),
        "causal_attention_mla": (mla, mla_shapes),
        "causal_attention_mla_grad": (_grad(mla), mla_shapes),
    }


@pytest.mark.parametrize("name", [
    "rmsnorm", "flash_attention", "rglru",
    "causal_attention_yi", "causal_attention_yi_grad",
    "causal_attention_mla", "causal_attention_mla_grad"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_cases()[name]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("repl,shard", [(1, 1), (2, 2)])
def test_bert10b_train_step_fits_v5e(topo, repl, shard):
    """The chip smoke's step: bert-10b at published widths, 4 layers,
    global batch 8 x seq 512, two micro-steps — on one chip and on the
    repl=2 x shard=2 mesh — fits each chip's HBM."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.mics import (
        MiCSConfig, batch_pspecs, build_train_step, init_state_shapes,
        make_batch_shapes, state_pspecs,
    )
    from repro.core.topology import MICS_AXES, MiCSTopology
    from repro.models.build import build_model
    from repro.optim.adamw import OptConfig

    devs = np.array(topo.devices[:repl * shard]).reshape(1, repl, shard, 1, 1)
    mtopo = MiCSTopology(Mesh(devs, MICS_AXES,
                              axis_types=(AxisType.Auto,) * len(MICS_AXES)))
    cfg = dataclasses.replace(get_config("bert-10b"), n_layers=4)
    model = build_model(cfg, tp=1)
    step = build_train_step(model, mtopo, MiCSConfig(micro_steps=2),
                            OptConfig(total_steps=8, warmup_steps=1))

    def placed(shapes, specs):
        return jax.tree.map(
            lambda s, p: _sds(s.shape, s.dtype, NamedSharding(mtopo.mesh, p)),
            shapes, specs, is_leaf=lambda x: isinstance(x, P))

    state = placed(init_state_shapes(model), state_pspecs(model, mtopo))
    batch = placed(make_batch_shapes(model, 8, 512, 2),
                   batch_pspecs(model, mtopo))
    compiled = step.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert per_device <= HBM_BYTES, per_device / 2**30
    if repl * shard > 1:
        # partition-group gathers, and hop 1 + hop 2 (the TPU compiler
        # lowers the hop-1 reduce-scatter to all-reduce ops too)
        text = compiled.as_text()
        for op in ("all-gather", "all-reduce"):
            assert op in text, op
