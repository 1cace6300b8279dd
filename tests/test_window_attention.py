"""``ops/window_attention.py`` against dense softmax (its own
``dense_attention``, written over the whole ``[L, L]`` mask) on the CPU,
where the kernel runs in Pallas' interpreter: causal x window x each
row's first real slot x head groups x tile sizes.

Tolerance: float32 operands, so the kernel and the dense softmax differ
by the order of their sums: 2e-6 of outputs of order 1 (readings under
6e-7)."""

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.window_attention import (
    dense_attention, key_steps, window_attention)

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


@pytest.mark.parametrize("leads", ["none", "ragged", "one_token"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_dense_softmax_over_the_mask(case, leads):
    B, Hq, Hkv, L, D, block, window = CASES[case][:7]
    Dv, scale = (CASES[case][7:] or (D, D ** -0.5))
    rng = np.random.default_rng(len(case))
    q, k, v = (jnp.asarray(rng.normal(size=(h, B, L, d)), jnp.float32)
               for h, d in ((Hq, D), (Hkv, D), (Hkv, Dv)))
    lead = {"none": np.zeros(B), "one_token": np.full(B, L - 1),
            "ragged": rng.integers(0, L, B)}[leads].astype(np.int32)
    got = np.asarray(window_attention(q, k, v, jnp.asarray(lead),
                                      scale=scale, window=window,
                                      block=block))
    assert got.shape == (Hq, B, L, Dv)
    want = np.asarray(dense_attention(q, k, v, jnp.asarray(lead),
                                      scale=scale, window=window))
    for b in range(B):  # slots before a row's first hold nothing defined
        np.testing.assert_allclose(got[:, b, lead[b]:], want[:, b, lead[b]:],
                                   atol=2e-6)


def test_keys_nobody_sees_move_nothing():
    """What lies before a row's first slot may hold anything finite
    (the program zeroes it): skipped or masked, it weighs exactly 0."""
    B, H, L, D, W = 2, 2, 32, 8, 8
    rng = np.random.default_rng(0)
    q, k, v = (rng.normal(size=(H, B, L, D)).astype(np.float32)
               for _ in range(3))
    lead = np.array([5, 20], np.int32)
    want = np.asarray(window_attention(*map(jnp.asarray, (q, k, v, lead)),
                                       scale=1.0, window=W, block=8))
    for b in range(B):
        k[:, b, :lead[b]] = v[:, b, :lead[b]] = 1e4
    got = np.asarray(window_attention(*map(jnp.asarray, (q, k, v, lead)),
                                      scale=1.0, window=W, block=8))
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
