"""``ops/delta_state.py`` on the CPU (the kernels interpreted): one token
a slot of a delta-rule state against a float64 oracle of the recurrence,
written out value by value; the stacked state's other layers untouched;
an idle slot bit for bit; the blocks of heads a grid step takes.  Both
kernels: a decay a channel on a head a tile (``state_update``), and a
decay a head on the state kept ``(d_k, heads x d_v)``, the heads along
the lanes (``head_decay_update``), at tiles that are no lane tile and
head counts that are no power of two."""

import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import delta_state
from bigdl_tpu.ops.delta_state import head_decay_update, state_update

#: float32 against float64 over sums of 8 to 128 products of order 1
TOL = 2e-5


def oracle(s, layer, decay, k, q, v, beta):
    """The module docstring's four lines, in float64, a slot and head
    at a time."""
    s = np.array(s, np.float64)
    out = np.zeros(v.shape, np.float64)
    for i in range(s.shape[1]):
        for h in range(s.shape[2]):
            tile = s[layer, i, h] * np.float64(decay[i, h])[:, None]
            read = tile.T @ np.float64(k[i, h])
            tile = tile + np.outer(
                np.float64(k[i, h]),
                np.float64(beta[i, h]) * (np.float64(v[i, h]) - read))
            out[i, h] = tile.T @ np.float64(q[i, h])
            s[layer, i, h] = tile
    return s, out


def draw(layers, slots, heads, dk, dv, seed=0):
    rng = np.random.default_rng(seed)

    def f(*shape):
        return rng.normal(size=shape).astype(np.float32)

    return dict(
        s=f(layers, slots, heads, dk, dv),
        decay=rng.uniform(0.01, 1.0, (slots, heads, dk)).astype(np.float32),
        k=f(slots, heads, dk), q=f(slots, heads, dk), v=f(slots, heads, dv),
        beta=rng.uniform(0.0, 1.0, (slots, heads)).astype(np.float32))


@pytest.mark.parametrize("layers,slots,heads,dk,dv,layer", [
    (1, 1, 1, 8, 8, 0), (2, 3, 4, 16, 8, 1), (3, 2, 6, 8, 24, 2),
    (2, 2, 16, 8, 16, 0), (1, 2, 32, 128, 128, 0)])
def test_the_kernel_is_the_recurrence(layers, slots, heads, dk, dv, layer):
    """From a toy to the served tile (32 heads of 128 x 128, 16 heads a
    grid step): the new state and the read from it, and no other layer
    of the stacked state moved."""
    a = draw(layers, slots, heads, dk, dv, seed=dk + heads)
    new, out = state_update(jnp.asarray(a["s"]), layer, a["decay"], a["k"],
                            a["q"], a["v"], a["beta"])
    want_s, want_o = oracle(a["s"], layer, a["decay"], a["k"], a["q"],
                            a["v"], a["beta"])
    scale = max(1.0, float(np.abs(want_s).max()))
    np.testing.assert_allclose(np.asarray(new), want_s, atol=TOL * scale)
    np.testing.assert_allclose(np.asarray(out), want_o,
                               atol=TOL * max(1.0, np.abs(want_o).max()))
    assert new.dtype == jnp.float32 and out.dtype == jnp.float32
    for other in range(layers):
        if other != layer:
            assert np.array_equal(np.asarray(new[other]), a["s"][other])


def test_the_read_comes_before_the_write_and_the_output_after():
    """One head, by hand: with ``S = I``, no decay and ``beta = 1`` the
    token reads ``k`` back, writes ``k (v - k)^T`` and the query reads
    the NEW state."""
    d = 8
    k = np.zeros((1, 1, d), np.float32)
    k[0, 0, 2] = 1.0
    v = np.arange(d, dtype=np.float32).reshape(1, 1, d)
    q = np.zeros((1, 1, d), np.float32)
    q[0, 0, 2] = 2.0
    s = np.eye(d, dtype=np.float32).reshape(1, 1, 1, d, d)
    new, out = state_update(jnp.asarray(s), 0, np.ones((1, 1, d), np.float32),
                            k, q, v, np.ones((1, 1), np.float32))
    want = np.eye(d, dtype=np.float32)
    want[2] = v[0, 0]            # row 2 replaced: e_2 + (v - e_2)
    np.testing.assert_array_equal(np.asarray(new[0, 0, 0]), want)
    np.testing.assert_array_equal(np.asarray(out[0, 0]), 2.0 * v[0, 0])


@pytest.mark.parametrize("heads,dk,dv", [(4, 16, 8), (32, 128, 128)])
def test_an_idle_slot_keeps_its_state_bit_for_bit(heads, dk, dv):
    """``decay = 1`` and ``beta = 0``: ``1 * S + k * 0``, whatever the
    slot's other inputs hold."""
    a = draw(2, 3, heads, dk, dv, seed=5)
    a["decay"][1] = 1.0
    a["beta"][1] = 0.0
    new, _ = state_update(jnp.asarray(a["s"]), 1, a["decay"], a["k"],
                          a["q"], a["v"], a["beta"])
    assert np.array_equal(np.asarray(new[:, 1]), a["s"][:, 1])
    assert not np.array_equal(np.asarray(new[1, 0]), a["s"][1, 0])
    assert not np.array_equal(np.asarray(new[1, 2]), a["s"][1, 2])


def test_the_layer_is_a_traced_scalar():
    """Six layers are one traced program: the layer rides in as a
    prefetched scalar, not as a constant of the kernel."""
    a = draw(3, 2, 4, 8, 8, seed=9)
    for layer in (0, 2):
        new, _ = state_update(jnp.asarray(a["s"]), jnp.int32(layer),
                              a["decay"], a["k"], a["q"], a["v"], a["beta"])
        want, _ = oracle(a["s"], layer, a["decay"], a["k"], a["q"], a["v"],
                         a["beta"])
        np.testing.assert_allclose(np.asarray(new), want, atol=TOL * 10)
    assert delta_state._program.cache_info().currsize >= 1


@pytest.mark.parametrize("heads,tile,want", [
    (32, 128 * 128 * 4, 16),    # the served shape: 1 MB of tiles a step
    (4, 16 * 8 * 4, 4),         # fewer than 8 heads: all of them
    (64, 8 * 8 * 4, 32),        # three rows a head fit 128: 42, whole
                                # sublane groups, a divisor: 32
    (8, 128 * 128 * 4, 8)])
def test_heads_a_grid_step(heads, tile, want):
    assert delta_state._heads_a_block(heads, tile) == want


def test_a_block_that_cannot_be_whole_sublane_groups_is_refused():
    with pytest.raises(ValueError, match="sublane"):
        delta_state._heads_a_block(20, 128 * 128 * 4)


# ----------------------------------------- a decay a head, heads along lanes
def lane_oracle(s, layer, decay, k, q, v, beta):
    """The same four lines with ONE decay a head, on the state kept
    ``(layers, slots, d_k, heads x d_v)``, in float64."""
    s = np.array(s, np.float64)
    heads = decay.shape[1]
    dv = s.shape[-1] // heads
    out = np.zeros(v.shape, np.float64)
    for i in range(s.shape[1]):
        for h in range(heads):
            at = slice(h * dv, (h + 1) * dv)
            tile = s[layer, i, :, at] * np.float64(decay[i, h])
            read = tile.T @ np.float64(k[i, h])
            tile = tile + np.outer(
                np.float64(k[i, h]),
                np.float64(beta[i, h]) * (np.float64(v[i, at]) - read))
            out[i, at] = tile.T @ np.float64(q[i, h])
            s[layer, i, :, at] = tile
    return s, out


def lane_draw(layers, slots, heads, dk, dv, seed=0):
    rng = np.random.default_rng(seed)

    def f(*shape):
        return rng.normal(size=shape).astype(np.float32)

    return dict(
        s=f(layers, slots, dk, heads * dv),
        decay=rng.uniform(0.01, 1.0, (slots, heads)).astype(np.float32),
        k=f(slots, heads, dk), q=f(slots, heads, dk),
        v=f(slots, heads * dv),
        # beta in (0, 2): the doubled write strength
        beta=rng.uniform(0.0, 2.0, (slots, heads)).astype(np.float32))


@pytest.mark.parametrize("layers,slots,heads,dk,dv,layer", [
    (1, 1, 1, 8, 8, 0),         # one lane tile, partly filled
    (2, 3, 6, 24, 48, 1),       # the tests' ratios: 2.25 lane tiles, up to
                                # four heads in one
    (2, 16, 4, 16, 64, 1),      # 8 slots a grid step, two heads a tile
    (1, 8, 2, 8, 192, 0),       # 192 lanes a head: the middle tile shared
    (1, 2, 30, 96, 192, 0)])    # the served tile: 30 heads of 96 x 192
def test_the_lane_kernel_is_the_recurrence(layers, slots, heads, dk, dv,
                                           layer):
    a = lane_draw(layers, slots, heads, dk, dv, seed=dk + heads)
    new, out = head_decay_update(jnp.asarray(a["s"]), layer, a["decay"],
                                 a["k"], a["q"], a["v"], a["beta"])
    want_s, want_o = lane_oracle(a["s"], layer, a["decay"], a["k"], a["q"],
                                 a["v"], a["beta"])
    scale = max(1.0, float(np.abs(want_s).max()))
    np.testing.assert_allclose(np.asarray(new), want_s, atol=TOL * scale)
    np.testing.assert_allclose(np.asarray(out), want_o,
                               atol=TOL * max(1.0, np.abs(want_o).max()))
    assert new.dtype == jnp.float32 and out.dtype == jnp.float32
    assert new.shape == a["s"].shape and out.shape == a["v"].shape
    for other in range(layers):
        if other != layer:
            assert np.array_equal(np.asarray(new[other]), a["s"][other])


@pytest.mark.parametrize("heads,dk,dv", [(6, 24, 48), (30, 96, 192)])
def test_an_idle_slot_keeps_its_lane_state_bit_for_bit(heads, dk, dv):
    a = lane_draw(2, 3, heads, dk, dv, seed=5)
    a["decay"][1] = 1.0
    a["beta"][1] = 0.0
    new, _ = head_decay_update(jnp.asarray(a["s"]), 1, a["decay"], a["k"],
                               a["q"], a["v"], a["beta"])
    assert np.array_equal(np.asarray(new[:, 1]), a["s"][:, 1])
    assert not np.array_equal(np.asarray(new[1, 0]), a["s"][1, 0])
    assert not np.array_equal(np.asarray(new[1, 2]), a["s"][1, 2])


def test_the_lane_kernels_layer_is_a_traced_scalar():
    a = lane_draw(3, 2, 6, 24, 48, seed=9)
    for layer in (0, 2):
        new, _ = head_decay_update(jnp.asarray(a["s"]), jnp.int32(layer),
                                   a["decay"], a["k"], a["q"], a["v"],
                                   a["beta"])
        want, _ = lane_oracle(a["s"], layer, a["decay"], a["k"], a["q"],
                              a["v"], a["beta"])
        np.testing.assert_allclose(np.asarray(new), want, atol=TOL * 10)
    assert delta_state._lane_program.cache_info().currsize >= 1


@pytest.mark.parametrize("slots,heads,dk,dv,want", [
    (256, 30, 96, 192, (8, 2)),     # the served shape: 3 lane tiles, 1.2 MB
    (256, 32, 128, 128, (8, 2)),    # a lane tile a head: doubled to 1 MB
    (3, 6, 24, 48, (3, 6)),         # no multiple of 8 slots: all of them;
                                    # 8 heads would fill tiles, 6 do not
                                    # divide: all the heads
    (16, 8, 16, 64, (8, 8)),        # 2 heads fill a tile; doubled twice
    (8, 2, 8, 192, (8, 2))])
def test_slots_and_heads_a_lane_grid_step(slots, heads, dk, dv, want):
    assert delta_state._lane_block(slots, heads, dk, dv) == want


@pytest.mark.parametrize("slots,heads,dk,dv", [
    (100, 30, 96, 192),     # 100 slots are one block of 14 MiB
    (8, 2, 20, 64),         # keys that are no whole sublane group
    (8, 2, 256, 64)])       # keys past the transposed tile
def test_a_lane_block_that_cannot_be_is_refused(slots, heads, dk, dv):
    with pytest.raises(ValueError, match="grid step"):
        delta_state._lane_block(slots, heads, dk, dv)
