"""``ops/window_attention.py`` against dense softmax (its own
``dense_attention``, written over the whole ``[L, L]`` mask) on the CPU,
where the kernel runs in Pallas' interpreter: causal x window x each
row's first real slot x head groups x tile sizes x the operands' layout
(one head a group, every head in one group's lanes, two groups; a score
of one product or of two).

Tolerance: float32 operands, so the kernel and the dense softmax differ
by the order of their sums: 2e-6 of outputs of order 1 (readings under
6e-7)."""

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.window_attention import (
    dense_attention, heads_first, key_steps, window_attention)

#: (rows, query heads, key-value heads, slots, head size, tile, window[,
#: value size, scale]): values as wide as the head and ``D ** -0.5``
#: where the last two are left out
CASES = {
    "full_four_tiles": (3, 4, 2, 32, 16, 8, None),
    "full_one_tile": (2, 2, 1, 16, 8, 16, None),
    "full_six_heads_a_group": (2, 6, 1, 24, 8, 8, None),
    "window_the_tile": (3, 6, 2, 32, 16, 8, 8),
    "window_over_a_tile": (2, 4, 4, 40, 16, 8, 12),
    "window_under_a_tile": (2, 8, 2, 32, 16, 16, 5),
    "window_of_one": (2, 2, 2, 16, 8, 8, 1),
    "window_over_the_row": (2, 4, 2, 16, 8, 8, 64),
    # latent attention expanded: as many key-value heads as query heads,
    # a head 16 + 8 wide against values 16 wide, a scale handed in
    "values_narrower_than_keys": (3, 4, 4, 32, 24, 8, None, 16, 0.29),
    "values_wider_than_keys": (2, 4, 2, 16, 8, 8, None, 24, 0.5),
    "values_narrower_under_a_window": (2, 4, 4, 32, 24, 8, 12, 16, 0.29),
}


#: how many groups ``G`` the heads of an operand lie in: one head a
#: group (``[heads, B, L, D]``), all in one group's lanes (``[1, B, L,
#: heads x D]``: what a projection writes), or two (an operand with an
#: odd head count keeps one)
LAYOUTS = {"heads_first": lambda heads: heads, "token_major": lambda heads: 1,
           "two_groups": lambda heads: 2 - heads % 2}


def grouped(a, layout):
    """``a [heads, B, L, D]`` in ``layout``: ``[G, B, L, heads / G x D]``."""
    H, B, L, D = a.shape
    G = LAYOUTS[layout](H)
    return jnp.moveaxis(jnp.asarray(a).reshape(G, H // G, B, L, D), 1,
                        3).reshape(G, B, L, H // G * D)


def the_leads(leads, B, L, rng):
    return {"none": np.zeros(B), "one_token": np.full(B, L - 1),
            "ragged": rng.integers(0, L, B)}[leads].astype(np.int32)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("leads", ["none", "ragged", "one_token"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_dense_softmax_over_the_mask(case, leads, layout):
    B, Hq, Hkv, L, D, block, window = CASES[case][:7]
    Dv, scale = (CASES[case][7:] or (D, D ** -0.5))
    rng = np.random.default_rng(len(case))
    q, k, v = (jnp.asarray(rng.normal(size=(h, B, L, d)), jnp.float32)
               for h, d in ((Hq, D), (Hkv, D), (Hkv, Dv)))
    lead = the_leads(leads, B, L, rng)
    got = window_attention(*(grouped(a, layout) for a in (q, k, v)),
                           jnp.asarray(lead), scale=scale, window=window,
                           block=block, head_dim=D)
    G = LAYOUTS[layout](Hq)
    assert got.shape == (G, B, L, Hq // G * Dv)
    got = np.asarray(heads_first(got, Dv))
    want = np.asarray(dense_attention(q, k, v, jnp.asarray(lead),
                                      scale=scale, window=window))
    for b in range(B):  # slots before a row's first hold nothing defined
        np.testing.assert_allclose(got[:, b, lead[b]:], want[:, b, lead[b]:],
                                   atol=2e-6)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("leads", ["none", "ragged"])
@pytest.mark.parametrize("window", [None, 12])
def test_a_score_of_two_products_is_one_over_the_concatenation(
        window, leads, layout):
    """Latent attention expanded: each head's own 16 dimensions beside 8
    rotated ones whose key is ONE for all heads. The kernel takes the
    two halves apart (the shared key once, never repeated); dense
    softmax takes the 24-wide operands concatenated."""
    B, H, L, D, D2, Dv, block, scale = 3, 4, 32, 16, 8, 16, 8, 0.29
    rng = np.random.default_rng(7)
    q, k, v, q2, k2 = (
        jnp.asarray(rng.normal(size=(h, B, L, d)), jnp.float32)
        for h, d in ((H, D), (H, D), (H, Dv), (H, D2), (1, D2)))
    lead = the_leads(leads, B, L, rng)
    got = window_attention(*(grouped(a, layout) for a in (q, k, v)),
                           jnp.asarray(lead), grouped(q2, layout), k2,
                           scale=scale, window=window, block=block,
                           head_dim=D)
    got = np.asarray(heads_first(got, Dv))
    want = np.asarray(dense_attention(
        jnp.concatenate([q, q2], -1),
        jnp.concatenate([k, jnp.broadcast_to(k2, (H, B, L, D2))], -1), v,
        jnp.asarray(lead), scale=scale, window=window))
    for b in range(B):
        np.testing.assert_allclose(got[:, b, lead[b]:], want[:, b, lead[b]:],
                                   atol=2e-6)
    # and the dense form given the halves is the same function
    np.testing.assert_allclose(
        np.asarray(dense_attention(
            grouped(q, layout), grouped(k, layout), v, jnp.asarray(lead),
            grouped(q2, layout), k2, scale=scale, window=window,
            head_dim=D)), want, atol=1e-6)


@pytest.mark.parametrize("layout", ["heads_first", "token_major"])
def test_keys_nobody_sees_move_nothing(layout):
    """What lies before a row's first slot may hold anything finite
    (the program zeroes it): skipped or masked, it weighs exactly 0."""
    B, H, L, D, W = 2, 2, 32, 8, 8
    rng = np.random.default_rng(0)
    q, k, v = (rng.normal(size=(H, B, L, D)).astype(np.float32)
               for _ in range(3))
    lead = np.array([5, 20], np.int32)

    def run():
        return np.asarray(heads_first(window_attention(
            *(grouped(a, layout) for a in (q, k, v)), jnp.asarray(lead),
            scale=1.0, window=W, block=8, head_dim=D), D))

    want = run()
    for b in range(B):
        k[:, b, :lead[b]] = v[:, b, :lead[b]] = 1e4
    got = run()
    for b in range(B):
        np.testing.assert_array_equal(got[:, b, lead[b]:],
                                      want[:, b, lead[b]:])
    # the last query of a row sees exactly its window: one query's
    # softmax over the W last keys
    s = np.einsum("hbd,hbkd->hbk", q[:, :, -1], k[:, :, -W:])
    p = np.exp(s - s.max(-1, keepdims=True))
    alone = np.einsum("hbk,hbkd->hbd", p / p.sum(-1, keepdims=True),
                      v[:, :, -W:])
    np.testing.assert_allclose(got[:, :, -1], alone, atol=2e-6)


@pytest.mark.parametrize("n,block,window,steps", [
    (8, 512, None, 8), (8, 512, 512, 2), (16, 256, 512, 3),
    (4, 1024, 512, 2), (8, 512, 513, 2), (8, 512, 514, 3), (1, 512, 512, 1), (8, 8, 1, 1)])
def test_a_windows_grid_is_as_long_as_the_tiles_it_spans(n, block, window,
                                                         steps):
    assert key_steps(n, block, window) == steps


def test_slots_must_divide_into_tiles():
    a = jnp.zeros((2, 1, 24, 8))
    with pytest.raises(ValueError):
        window_attention(a, a, a, jnp.zeros((1,), jnp.int32), scale=1.0,
                         block=16)
    with pytest.raises(ValueError):
        window_attention(jnp.zeros((3, 1, 16, 8)), a[:, :, :16], a[:, :, :16],
                         jnp.zeros((1,), jnp.int32), scale=1.0, block=8)


def test_lanes_must_be_whole_heads():
    a = jnp.zeros((1, 1, 16, 24))
    with pytest.raises(ValueError):  # 24 lanes are not heads of 16
        window_attention(a, a, a, jnp.zeros((1,), jnp.int32), scale=1.0,
                         block=8, head_dim=16)
    with pytest.raises(ValueError):  # a second query without its key
        window_attention(a, a, a, jnp.zeros((1,), jnp.int32), a, scale=1.0,
                         block=8, head_dim=8)
