"""Topology invariants: group tables, heuristics, mesh refactoring, and the
HLO analyzer cross-checked against XLA's own cost analysis on a loop-free
program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, strategies as st
from repro.core.topology import (
    HBM_BYTES_PER_CHIP, MiCSTopology, choose_partition_size, make_host_mesh,
)

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")


def test_partition_and_replication_groups_cover_world():
    topo = MiCSTopology(make_host_mesh(1, 1, 1, 1))
    assert topo.partition_groups() == [[0]]
    assert topo.world_size == 1
    assert topo.data_parallel_size == 1


@given(st.integers(28, 36), st.integers(0, 3))
def test_choose_partition_size_monotone(log2_params, reserve_step):
    params = 2 ** log2_params
    reserve = 0.2 + 0.05 * reserve_step
    p = choose_partition_size(params, reserve_fraction=reserve)
    assert p in (1, 2, 4, 8, 16)
    # p is minimal: p/2 must NOT fit (when p > 1)
    budget = HBM_BYTES_PER_CHIP * (1 - reserve)
    per_dev = params * 16 / 16
    if p > 1:
        assert per_dev / (p // 2) > budget
    assert per_dev / p <= budget


def test_choose_partition_size_known_models():
    from repro.configs import get_config
    from repro.models.build import exact_param_count

    p_qwen = choose_partition_size(exact_param_count(get_config("qwen1.5-110b")))
    p_1b = choose_partition_size(exact_param_count(get_config("llama3.2-1b")))
    assert p_qwen == 16
    assert p_1b == 1


def test_too_large_model_raises():
    with pytest.raises(ValueError):
        choose_partition_size(10_000_000_000_000)


def test_hlo_analyzer_matches_xla_on_loop_free_program():
    """Without loops the trip-weighted analyzer must agree with XLA's own
    cost analysis on matmul FLOPs."""
    from repro.roofline.hlo_stats import analyze

    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 256), jnp.float32)
    comp = jax.jit(lambda a, b: (a @ b) @ (a @ b).T).lower(a, b).compile()
    got = analyze(comp.as_text(), {"d": 1})
    ca = comp.cost_analysis()
    np.testing.assert_allclose(got["dot_flops"], ca["flops"], rtol=1e-6)


def test_hlo_analyzer_weights_scan_trip_counts():
    from repro.roofline.hlo_stats import analyze

    def f(xs):
        def body(c, x):
            return c + jnp.sum(x @ x), None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return out

    xs = jnp.ones((7, 32, 32), jnp.float32)
    comp = jax.jit(f).lower(xs).compile()
    got = analyze(comp.as_text(), {"d": 1})
    ca = comp.cost_analysis()
    # XLA counts the body once; the analyzer must count it 7 times.
    assert got["dot_flops"] == pytest.approx(7 * 2 * 32 * 32 * 32, rel=1e-6)
    assert ca["flops"] < got["dot_flops"]
