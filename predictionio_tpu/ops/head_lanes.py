"""What a prefill does to each head on its own, with the heads lying in
the LANES of a token-major array ``[T, heads x D]`` (the layout a
projection writes and ``ops/window_attention.py`` reads): the per-head
RMSNorm and rotary of the queries and keys, and the head gate on the
attention's output. One pass over the array each.

Written as XLA operations both want a ``[T, heads, D]`` view, and a
reshape between ``[T, heads x D]`` and it crosses the memory tiles (a
tile is 8 or 16 TOKENS of 128 lanes; the view's is 8 or 16 HEADS of one
token). The compiler lays the view out some other way and pays for it:
the norm and rotary of a sliding layer's 64 heads at 32,768 slots were
five passes over a float32 gigabyte (16-20 ms), the gate a standalone
cast, a product and a multiply (10.7 ms against the 3.5 it took heads
first; PR 38's builder's chip runs). Here a block is ONE head's lanes
of a few thousand tokens (``BlockSpec((tile, D), (i, h))``: whole lane
tiles of the row): the mean of squares is a reduction along the lanes,
rotate-half is a rotation of the lanes (``pltpu.roll``) against signed
sine tables, a head's gate is one column broadcast along its lanes.

``head_norm_rotary`` and ``head_gate`` are ``custom-call`` operations of
those names in a device trace. ``D`` is a multiple of 128 (the caller
asks: ``models/decoder.py::_groups``); a last block the tokens do not
fill is Pallas' to pad and cut (a token is its own row). Off the TPU
they run in Pallas' interpreter.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .window_attention import _interpreted

#: tokens a block: one head of 2,048 tokens is 1 MB of float32 coming
#: and half of that going, twice each for the pipeline, beside 3 MB of
#: rotary tables. A block is ONE head: the body is one head's
#: arithmetic, not a loop over heads unrolled into the kernel's text,
#: which every start of a program lowers again
TILE = 2048


def _norm_rotary_kernel(x_ref, t_ref, *refs, R: int, eps: float):
    *gain_ref, o_ref = refs  # the norm's gain, where there is a norm
    y, D = x_ref[...], x_ref.shape[1]
    if gain_ref:
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=1, keepdims=True)
                              + eps) * gain_ref[0][...]
    # lane i of the first rotated half meets lane i + R/2, and the other
    # way round; the tables are zero where a lane has no partner
    out = y * t_ref[0] + pltpu.roll(y, D - R // 2, 1) * t_ref[1] \
        + pltpu.roll(y, R // 2, 1) * t_ref[2]
    o_ref[...] = out.astype(o_ref.dtype)


def rotary_tables(pos: jax.Array, rope, D: int) -> jax.Array:
    """``[3, T, D]`` float32 for rotate-half over the first ``R = 2
    len(inv)`` of a head's ``D`` dimensions at positions ``pos [T]``:
    what a lane is multiplied by itself (cos, 1 past ``R``), what the
    lane ``R/2`` ABOVE it is (-sin, on the first half) and what the lane
    ``R/2`` BELOW it is (+sin, on the second): ``x1 cos - x2 sin | x2 cos
    + x1 sin | the rest`` as three products and two sums a lane."""
    inv, factor = rope
    R = 2 * len(inv)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    none, rest = jnp.zeros_like(sin), jnp.zeros((pos.shape[0], D - R))
    return jnp.stack([
        jnp.concatenate([cos, cos, rest + 1.0], axis=1),
        jnp.concatenate([-sin, none, rest], axis=1),
        jnp.concatenate([none, sin, rest], axis=1)])


@functools.partial(jax.jit, static_argnames=("rope", "head_dim", "eps",
                                             "dtype", "tile"))
def head_norm_rotary(x: jax.Array, gain: Optional[jax.Array],
                     pos: jax.Array, *,
                     rope: Tuple[Tuple[float, ...], float], head_dim: int,
                     eps: float, dtype, tile: int = TILE) -> jax.Array:
    """``x [T, heads x D]`` float32 at positions ``pos [T]`` -> the same
    in ``dtype``: every head ``x_h * rsqrt(mean(x_h^2) + eps) * gain
    [D]`` (``gain`` ``None``: no norm), then rotate-half over its first
    ``2 len(rope[0])`` dimensions (``rope``: ``DecoderConfig.rope``'s
    inverse frequencies and factor on cos and sin)."""
    T, W = x.shape
    D = head_dim
    block = pl.BlockSpec((tile, D), lambda i, h: (i, h))
    ins = [x.astype(jnp.float32), rotary_tables(pos, rope, D)]
    specs = [block, pl.BlockSpec((3, tile, D), lambda i, h: (0, i, 0))]
    if gain is not None:
        ins.append(gain.astype(jnp.float32).reshape(1, D))
        specs.append(pl.BlockSpec((1, D), lambda i, h: (0, 0)))
    return pl.pallas_call(
        functools.partial(_norm_rotary_kernel, R=2 * len(rope[0]), eps=eps),
        grid=(pl.cdiv(T, tile), W // D), in_specs=specs, out_specs=block,
        out_shape=jax.ShapeDtypeStruct((T, W), jnp.dtype(dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpreted(), name="head_norm_rotary",
    )(*ins)


def _gate_kernel(o_ref, g_ref, out_ref):
    out_ref[...] = (o_ref[...].astype(jnp.float32)
                    * g_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("head_dim", "tile"))
def head_gate(o: jax.Array, gate: jax.Array, *, head_dim: int,
              tile: int = TILE) -> jax.Array:
    """``o [T, heads x D]`` times ``gate [T, heads]`` float32, a head's
    scalar along its ``D`` lanes: a float32 product, rounded once to
    ``o``'s dtype."""
    T, W = o.shape
    D = head_dim
    block = pl.BlockSpec((tile, D), lambda i, h: (i, h))
    return pl.pallas_call(
        _gate_kernel, grid=(pl.cdiv(T, tile), W // D),
        # a head's gates as a column of their own: [heads, T, 1]
        in_specs=[block, pl.BlockSpec((None, tile, 1),
                                      lambda i, h: (h, i, 0))],
        out_specs=block, out_shape=jax.ShapeDtypeStruct((T, W), o.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpreted(), name="head_gate",
    )(o, gate.astype(jnp.float32).T[..., None])
