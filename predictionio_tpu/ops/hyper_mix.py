"""The residual path of ``hc_mult`` streams as two kernels a sub-block
(``models/decoder.py::_sub_block``): the streams are read twice and
written once, and nothing else their size is.

``x [n, T, H]`` float32 holds the ``n`` residual streams of ``T``
tokens. A sub-block reads ``u = sum_j pre_j x_j``, runs its operator
``F`` on the normalised ``u`` and writes ``x'_i = sum_j res[i, j] x_j +
post_i F(.)``; ``pre``, ``post [n]`` and ``res [n, n]`` are a token's
own, from the projection of its ``n H`` values onto ``c = n (n + 2)``
columns (``_hc_coefficients`` has the arithmetic, and is what the tests
hold these kernels to). ``F`` lies between the read and the write, so
two kernels, both over tiles of ``TILE`` tokens with the whole row in
VMEM:

``hyper_mix_read`` takes a tile ``[n, t, H]`` ONCE and leaves ``z =
rms(u) * gain [T, H]`` and the ``c`` coefficients a token. With the tile
in hand: the squares' sum; the projection, float32 as ``highest`` gives
it, by hand: ``x`` split into three bfloat16 parts that add up to it,
each times ``phi``'s three parts laid SIDE BY SIDE in one ``[H, 128]``
block (``3 c = 72`` of the MXU's 128 columns where ``c`` alone filled
24: three passes, not six); the sum transposed so that the tokens lie
on the lanes, where ``sigmoid``, the clipped ``exp`` and the ``iters``
row-then-column passes over ``[n, n]`` a token are passes over ``n``
half-filled vectors; the coefficients transposed back, a column a
coefficient, as the streams' tiles want them; ``u``, its mean square
and the gain, chunk by chunk of the lanes.

``hyper_mix_write`` takes the tile again with ``F``'s output and the
coefficients and writes the STACKED ``[n, T, H]``, aliased onto ``x``
(the caller has no further use for it): no second copy of the streams.

Both are ``custom-call`` operations named ``hyper_mix_read`` and
``hyper_mix_write`` in a device trace. A last tile that the stream does
not fill is Pallas' to pad and to cut: every token is its own row in
both kernels, so what the padding holds reaches no real token. Off the
TPU they run in Pallas' interpreter (tests, rehearsals).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .window_attention import _interpreted

#: tokens a tile. A tile of four float32 streams 3584 wide is 7.3 MB; the
#: write kernel holds two of them coming and two going. Tiles of 64, 128
#: and 256 tokens read within 5 % of each other at 8,192-16,384 slots:
#: both kernels wait for their bytes (2.25 + 2.96 ms at 12,288 slots,
#: 553 GB/s: what float32 streams move at here; my chip runs, PR 36)
TILE = 128
LANES = 128
#: lanes of a row taken at a time inside a tile: ``[128, 512]`` float32
#: is the register file's size, and 3584 = 7 x 512
CHUNK = 512
#: two tiles coming, two going and two of the operator's output in the
#: write kernel: 33 MB
VMEM_LIMIT = 48 << 20


def _chunk(H: int) -> int:
    return next((c for c in (CHUNK, 256, LANES) if H % c == 0), H)


def _three(v):
    """``v`` float32 as three bfloat16 parts that add up to it."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = v.astype(bf16)
    r = v - hi.astype(f32)
    mid = r.astype(bf16)
    return hi, mid, (r - mid.astype(f32)).astype(bf16)


def _by_lanes(v):
    """``[t, k LANES] -> [t, LANES]``: the lane groups added up (whole
    vectors; the one sum across lanes is taken once a tile)."""
    if v.shape[1] % LANES:
        return v
    return sum(v[:, s:s + LANES] for s in range(0, v.shape[1], LANES))


def _read_kernel(x_ref, w_ref, sb_ref, gain_ref, z_ref, coef_ref, t_ref, *,
                 n: int, eps: float, norm_eps: float, clamp, iters: int):
    f32 = jnp.float32
    _, t, H = x_ref.shape
    c, width, step = n * (n + 2), w_ref.shape[-1], _chunk(H)
    acc = [jnp.zeros((t, width), f32) for _ in range(3)]
    sq = 0.0
    for j in range(n):
        for s in range(0, H, step):
            xs = x_ref[j, :, s:s + step]
            sq = sq + _by_lanes(xs * xs)
            w = w_ref[j, s:s + step, :]
            acc = [a + jnp.dot(part, w, preferred_element_type=f32)
                   for a, part in zip(acc, _three(xs))]
    # smallest first; column 3c carries the squares' sum through the
    # transpose
    lane = jax.lax.broadcasted_iota(jnp.int32, (t, width), 1)
    both = jnp.where(lane == 3 * c, jnp.sum(sq, axis=1, keepdims=True),
                     (acc[2] + acc[1]) + acc[0]).T       # [width, t]
    p = (both[2 * c:3 * c] + both[c:2 * c]) + both[:c]
    # ptpu: allow[unguarded-domain] — the mean over a token's n H values:
    # a shape
    p = p * jax.lax.rsqrt(both[3 * c:3 * c + 1] / (n * H) + eps)
    # the tokens on the lanes: [c, t], a row a coefficient
    t_ref[...] = jnp.zeros(t_ref.shape, f32)
    t_ref[:c, :] = sb_ref[0] * p + sb_ref[1]
    pre = jax.nn.sigmoid(t_ref[:n, :])
    post = 2.0 * jax.nn.sigmoid(t_ref[n:2 * n, :])
    res = [jnp.exp(jnp.clip(t_ref[(2 + i) * n:(3 + i) * n, :], *clamp))
           for i in range(n)]                            # res[i]: [n(j), t]
    for _ in range(iters):
        res = [r / (jnp.sum(r, axis=0, keepdims=True) + eps) for r in res]
        down = sum(res) + eps
        res = [r / down for r in res]
    t_ref[:n, :] = pre
    t_ref[n:2 * n, :] = post
    for i, r in enumerate(res):
        t_ref[(2 + i) * n:(3 + i) * n, :] = r
    coef = t_ref[...].T                                  # [t, LANES]
    coef_ref[...] = coef
    sq = 0.0
    for s in range(0, H, step):
        u = sum(coef[:, j:j + 1] * x_ref[j, :, s:s + step] for j in range(n))
        z_ref[:, s:s + step] = u
        sq = sq + _by_lanes(u * u)
    # ptpu: allow[unguarded-domain] — the mean over a row's H values
    scale = jax.lax.rsqrt(jnp.sum(sq, axis=1, keepdims=True) / H + norm_eps)
    for s in range(0, H, step):
        z_ref[:, s:s + step] = z_ref[:, s:s + step] * scale \
            * gain_ref[:, s:s + step]


def _write_kernel(x_ref, out_ref, coef_ref, y_ref, *, n: int):
    H = x_ref.shape[2]
    step = _chunk(H)
    coef = coef_ref[...]
    post = [coef[:, n + i:n + i + 1] for i in range(n)]
    res = [[coef[:, (2 + i) * n + j:(2 + i) * n + j + 1] for j in range(n)]
           for i in range(n)]
    for s in range(0, H, step):
        xs = [x_ref[j, :, s:s + step] for j in range(n)]
        out = out_ref[:, s:s + step]
        for i in range(n):
            y_ref[i, :, s:s + step] = sum(
                res[i][j] * xs[j] for j in range(n)) + post[i] * out


def _packed(phi, n: int, H: int):
    """``phi [n H, c]`` float32 as ``[n, H, width]`` bfloat16: its three
    parts side by side, then zeros. Rounded by ``reduce_precision``, not
    by a cast there and back: outside a kernel XLA takes such a pair of
    casts for excess precision it may keep, the remainders are then zero
    and ``phi`` is its first part alone (2^-9 of a coefficient, my chip
    run, PR 36)."""
    c = phi.shape[1]
    width = -(-(3 * c + 1) // LANES) * LANES
    rounded = functools.partial(jax.lax.reduce_precision, exponent_bits=8,
                                mantissa_bits=7)
    hi = rounded(phi.astype(jnp.float32))
    mid = rounded(phi - hi)
    w = jnp.concatenate([hi, mid, phi - hi - mid], axis=1)
    return jnp.pad(w.astype(jnp.bfloat16),
                   ((0, 0), (0, width - 3 * c))).reshape(n, H, width)


@functools.partial(jax.jit, static_argnames=(
    "eps", "norm_eps", "clamp", "iters", "tile"))
def hyper_mix_read(x: jax.Array, phi: jax.Array, scale: jax.Array,
                   bias: jax.Array, gain: jax.Array, *, eps: float,
                   norm_eps: float, clamp: Tuple[float, float], iters: int,
                   tile: int = TILE) -> Tuple[jax.Array, jax.Array]:
    """``x [n, T, H]`` float32, ``phi [n H, c]`` (columns: ``pre``,
    ``post``, ``res`` row by row), ``scale``, ``bias [c]`` (the column's
    ``a`` and ``b``), ``gain [H]`` -> ``(z [T, H], coef [T, LANES])``
    float32: ``z`` the normalised read, ``coef``'s first ``c`` columns
    the token's coefficients in ``phi``'s order (:func:`coefficients`)."""
    n, T, H = x.shape
    f32 = jnp.float32
    c, (rows, columns) = n * (n + 2), phi.shape
    if (rows, columns) != (n * H, c) or 3 * c + 1 > LANES:
        raise ValueError(f"{n} streams of {H} against phi {phi.shape}")
    w = _packed(phi, n, H)
    sb = jnp.broadcast_to(jnp.stack([scale, bias]).astype(f32)[..., None],
                          (2, c, tile))
    whole = lambda i: (0, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_read_kernel, n=n, eps=eps, norm_eps=norm_eps,
                          clamp=clamp, iters=iters),
        grid=(pl.cdiv(T, tile),),
        in_specs=[pl.BlockSpec((n, tile, H), lambda i: (0, i, 0)),
                  pl.BlockSpec(w.shape, whole),
                  pl.BlockSpec(sb.shape, whole),
                  pl.BlockSpec((1, H), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((tile, H), lambda i: (i, 0)),
                   pl.BlockSpec((tile, LANES), lambda i: (i, 0))],
        scratch_shapes=[pltpu.VMEM((LANES, tile), f32)],
        out_shape=[jax.ShapeDtypeStruct((T, H), f32),
                   jax.ShapeDtypeStruct((T, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpreted(), name="hyper_mix_read",
    )(x.astype(f32), w, sb, gain.astype(f32).reshape(1, H))


@functools.partial(jax.jit, static_argnames=("tile",))
def hyper_mix_write(x: jax.Array, out: jax.Array, coef: jax.Array, *,
                    tile: int = TILE) -> jax.Array:
    """``x [n, T, H]`` float32, the operator's output ``out [T, H]`` and
    ``coef`` from :func:`hyper_mix_read` -> ``x' [n, T, H]``, in ``x``'s
    buffer where the caller leaves it to this call."""
    n, T, H = x.shape
    f32 = jnp.float32
    streams = pl.BlockSpec((n, tile, H), lambda i: (0, i, 0))
    return pl.pallas_call(
        functools.partial(_write_kernel, n=n),
        grid=(pl.cdiv(T, tile),),
        in_specs=[streams, pl.BlockSpec((tile, H), lambda i: (i, 0)),
                  pl.BlockSpec((tile, LANES), lambda i: (i, 0))],
        out_specs=streams,
        out_shape=jax.ShapeDtypeStruct((n, T, H), f32),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpreted(), name="hyper_mix_write",
    )(x.astype(f32), out.astype(f32), coef)


def coefficients(coef: jax.Array, n: int):
    """``coef [T, LANES]`` as ``_hc_coefficients`` lays them: ``(pre [n,
    T], post [n, T], res [n, n, T])``."""
    by = coef[:, :n * (n + 2)].T
    return by[:n], by[n:2 * n], by[2 * n:].reshape(n, n, -1)
