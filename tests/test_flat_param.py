"""Property-based tests of the flat parameter pool invariants (hypothesis)."""

import jax
import numpy as np

from hypothesis_compat import given, settings, strategies as st
from repro.core.flat_param import PAD_MULTIPLE, LayoutBuilder

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


shapes = st.lists(
    st.tuples(st.integers(1, 8), st.integers(1, 16)), min_size=1, max_size=8)


def _layout(dims):
    b = LayoutBuilder()
    for i, (a, c) in enumerate(dims):
        b.add(f"t{i}", (a, c), decay=(i % 2 == 0),
              init=["normal", "zeros", "ones"][i % 3])
    return b.build()


@given(shapes)
def test_roundtrip(dims):
    layout = _layout(dims)
    key = jax.random.key(0)
    flat = layout.init_flat(key)
    assert flat.shape == (layout.flat_len,)
    assert layout.flat_len % PAD_MULTIPLE == 0
    tensors = layout.unflatten(flat)
    flat2 = layout.flatten(tensors)
    np.testing.assert_array_equal(flat, flat2)
    # segments are contiguous and ordered
    cursor = 0
    for s in layout.segments:
        assert s.offset == cursor
        cursor += s.size
    assert cursor == layout.raw_len <= layout.flat_len


@given(shapes, st.integers(1, 8))
def test_shard_masks_tile_to_full(dims, nshards_pow):
    layout = _layout(dims)
    p = 2 ** (nshards_pow % 4)
    assert layout.flat_len % p == 0
    shard_len = layout.flat_len // p
    full_decay = np.concatenate([
        np.asarray(layout.decay_mask_for_shard(i * shard_len, shard_len))
        for i in range(p)
    ])
    full_pad = np.concatenate([
        np.asarray(layout.padding_mask_for_shard(i * shard_len, shard_len))
        for i in range(p)
    ])
    # padding tail masked out
    assert np.all(full_pad[layout.raw_len:] == 0)
    assert np.all(full_pad[: layout.raw_len] == 1)
    # decay mask honors per-segment decay flags
    for s in layout.segments:
        want = 1.0 if s.decay else 0.0
        assert np.all(full_decay[s.offset:s.end] == want), s.name
    assert np.all(full_decay[layout.raw_len:] == 0)


@given(shapes)
def test_init_kinds(dims):
    layout = _layout(dims)
    flat = layout.init_flat(jax.random.key(1))
    tensors = layout.unflatten(flat)
    for i, s in enumerate(layout.segments):
        t = np.asarray(tensors[s.name])
        if s.init == "zeros":
            assert np.all(t == 0)
        elif s.init == "ones":
            assert np.all(t == 1)
        else:
            assert np.std(t) > 0 or t.size < 4


@given(shapes)
def test_init_flat_draws_equal_shaped_draws(dims):
    """init_flat draws each segment flat; the values are those of a draw in
    the segment's own shape, so the flat draw changes no initial weight."""
    layout = _layout(dims)
    key = jax.random.key(2)
    tensors = layout.unflatten(layout.init_flat(key))
    for s in layout.segments:
        key, sub = jax.random.split(key)
        if s.init == "normal":
            want = jax.random.normal(sub, s.shape) * s.std
            np.testing.assert_array_equal(tensors[s.name], want)
