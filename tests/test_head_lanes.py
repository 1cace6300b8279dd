"""``ops/head_lanes.py`` against the plain lines of ``models/decoder.py``
(``_rms`` and ``_rotary`` over a ``[T, heads, D]`` view, the gate as a
broadcast over it) on the CPU, where the kernels run in Pallas'
interpreter: full and partial rotations, a last block the tokens do not
fill, one head and several.

Tolerance: float32 throughout; the kernel sums a head's squares in
another order than the view's reduction: 3e-6 of outputs of order 1
(readings under 1.5e-6; an unnormalised head three times as large reads three times that). The gate is a product rounded once: exact."""

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import decoder
from predictionio_tpu.ops import head_lanes

#: (tokens, heads, head width, rotated width, tokens a block)
CASES = {
    "the_whole_head_rotated": (40, 3, 16, 16, 16),
    "half_the_head_rotated": (40, 3, 16, 8, 16),
    "two_rotated_of_sixteen": (24, 2, 16, 2, 8),
    "a_last_block_not_filled": (37, 4, 16, 16, 16),
    "one_block_of_tokens": (8, 6, 8, 8, 2048),
    "one_head": (32, 1, 16, 8, 16),
}


def _rope(R, factor=1.3):
    return tuple(1.0 / 100 ** (np.arange(0, R, 2) / R)), factor


@pytest.mark.parametrize("norm", [True, False], ids=["norm", "no_norm"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_norm_and_rotary_in_the_lanes_are_the_view_s(case, dtype, norm):
    T, heads, D, R, tile = CASES[case]
    rng = np.random.default_rng(len(case))
    x = jnp.asarray(rng.normal(size=(T, heads * D)) * 3, jnp.float32)
    gain = jnp.asarray(rng.normal(size=(D,)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 4096, T), jnp.int32)
    got = head_lanes.head_norm_rotary(
        x, gain if norm else None, pos, rope=_rope(R), head_dim=D, eps=1e-6,
        dtype=dtype, tile=tile)
    by_head = x.reshape(T, heads, D)
    if norm:
        by_head = decoder._rms(by_head, gain, 1e-6)
    want = decoder._rotary(by_head, pos, _rope(R)).reshape(T, -1)
    assert got.dtype == jnp.dtype(dtype) and got.shape == x.shape
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=3e-6 if norm else 1e-5)
    else:  # one rounding of the same float32
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(want.astype(jnp.bfloat16), np.float32),
            atol=2.0 ** -7, rtol=2.0 ** -7)


def test_the_tables_are_zero_where_a_lane_has_no_partner():
    """Half a head rotated: lanes 0-3 meet 4-7 (minus sine), 4-7 meet
    0-3 (plus sine), 8-15 pass through at 1."""
    cos, lo, hi = np.asarray(head_lanes.rotary_tables(
        jnp.arange(5), _rope(8, factor=1.0), 16))
    assert (cos[:, 8:] == 1).all() and (lo[:, 4:] == 0).all()
    assert (hi[:, :4] == 0).all() and (hi[:, 8:] == 0).all()
    np.testing.assert_allclose(lo[:, :4], -hi[:, 4:8])
    np.testing.assert_allclose(cos[:, :4] ** 2 + hi[:, 4:8] ** 2, 1.0,
                               atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_gate_in_the_lanes_is_the_broadcast_over_the_view(case, dtype):
    T, heads, D, _, tile = CASES[case]
    rng = np.random.default_rng(len(case))
    o = jnp.asarray(rng.normal(size=(T, heads * D)), dtype)
    gate = jnp.asarray(rng.uniform(size=(T, heads)), jnp.float32)
    got = head_lanes.head_gate(o, gate, head_dim=D, tile=tile)
    want = (o.astype(jnp.float32).reshape(T, heads, D)
            * gate[..., None]).reshape(T, -1).astype(dtype)
    assert got.dtype == o.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
