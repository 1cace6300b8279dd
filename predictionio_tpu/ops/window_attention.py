"""Blockwise causal attention over right-aligned rows, with an optional
sliding window: the generative prefill's attention (``models/decoder.py``).

Layout: HEADS LIE IN THE LANES. Every operand and the output is ``[G, B,
L, Hi x D]``: ``B`` rows of up to ``L`` tokens, the heads in ``G`` groups
of ``Hi``, a group's heads side by side along the minor axis, ``D`` lanes
each (``head_dim``, static). ``q [Gq, B, L, Hiq x D]``, ``k [Gk, B, L,
Hik x D]``, ``v [Gv, B, L, Hiv x Dv]`` (``Dv`` is ``D`` in the
grouped-query families and 128 beside latent attention's wider keys);
each has its own ``G`` and the output has ``q``'s: ``o [Gq, B, L, Hiq x
Dv]`` in ``q``'s dtype. ``G = 1`` is token-major, what a projection ``[T,
H] x [H, heads x D]`` writes and what ``W_o`` reads: nothing between the
matrix products and this kernel changes a layout. ``G = heads`` (``Hi =
1``) is heads first, the kernel's only layout until PR 39. Groups
between the two are what a projection taken a few heads at a time
stacks. The kernel's tiles are ``(block, D)`` whatever the groups: the
block index picks head ``h`` as lanes ``(h % Hi) D ..`` of group ``h //
Hi``. On the chip a head in the lanes must fill whole lane tiles (``D``
a multiple of 128); a narrower head takes ``Hi = 1``, where the block is
the whole minor axis (Pallas' interpreter does not mind either way).

Rows are RIGHT-aligned: row ``b``'s tokens lie in slots ``lead[b] ..
L-1`` (the layout the decode's cache keeps), so a slot's index is its
position plus ``lead[b]`` and causal order is slot order. Query slot
``i`` sees key slots ``max(lead, i - window + 1) .. i`` (``window``
``None``: all from ``lead``). Query head ``h`` reads key-value head ``h
// (Hq / Hkv)`` through the block index, so ``k`` and ``v`` are never
repeated in memory. Slots before ``lead[b]`` of the output hold nothing
defined (blocks of pad slots are not computed and not written). What
``k`` and ``v`` hold before ``lead[b]`` weighs exactly 0 as long as it
is finite.

A score of TWO products: with ``q2 [G, B, L, Hi x D2]`` and ``k2 [G', B,
L, Hi' x D2]`` (as many heads as ``q``; ``k2``'s a divisor of them,
chosen by the block index like ``k``'s) the score is ``q . k + q2 . k2``,
two float32 partial products summed before the scale. Latent attention's
expanded prefill is that: each head's own ``q_nope . k_nope`` plus its
rotated half against the rotated key, which is ONE for all heads
(``k2``'s one head) and is never concatenated 32 times over.

One Pallas kernel (its name in a device trace: ``window_attention``, a
``custom-call``), grid ``(B, Hq, L / block, key steps)``. A tile of
``block`` queries meets one tile of ``block`` keys a step: scores
float32 from bfloat16 operands, the running maximum, sum and
accumulator float32 in VMEM (online softmax), the output normalised
once on the last step. Tiles no query of the tile can see are SKIPPED,
not masked: above the diagonal, older than the window, before the row's
first token (``lead`` is prefetched into SMEM, so the skipping follows
each row's real length). A windowed layer's key axis of the grid is
only as long as the tiles a window spans (2 at ``block = window``), and
a skipped step maps to the tile it already holds, so it moves nothing.
Tiles fully inside the mask take no mask arithmetic.

Off the TPU the kernel runs in Pallas' interpreter (tests, rehearsals),
and says so once in the log: a deployment there is slow, not wrong.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: queries and keys a tile: 1024 x 1024 float32 scores are 4 MB of VMEM.
#: Over 16 rows of 4,096 slots (histories 289 .. 4,096) a full layer of
#: 48 heads took 90.9 / 33.2 / 18.9 ms at 256 / 512 / 1024 and a layer
#: of 64 heads under a window of 512 37.0 / 19.2 / 18.7 (my chip run,
#: PR 32): a step costs about as much skipped as a small tile computed
BLOCK = 1024
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)  # exp(_NEG - _NEG) is 1

log = logging.getLogger(__name__)


@functools.lru_cache(maxsize=None)
def _interpreted() -> bool:
    """No TPU backend: Pallas' interpreter takes this package's kernels
    (said once; ``ops/moe.py`` and ``ops/hyper_mix.py`` ask here too)."""
    off_chip = jax.default_backend() != "tpu"
    if off_chip:
        log.warning("backend %s, not tpu: the Pallas kernels "
                    "(window_attention, head_lanes, touched_experts, "
                    "hyper_mix) run in Pallas' interpreter",
                    jax.default_backend())
    return off_chip


def key_steps(n_blocks: int, block: int, window: Optional[int]) -> int:
    """Key tiles a query tile can need: all up to its own (causal), or
    those a window spans."""
    if window is None:
        return n_blocks
    return min(n_blocks, max(
        i - max(i * block - window + 1, 0) // block + 1
        for i in range(n_blocks)))


def _div(a, n: int):
    """``a // n`` and ``a % n`` of a traced index that is never negative
    (``lax.div`` and ``lax.rem`` round towards zero: the same there).
    Floor division lowers through sign arithmetic, in every index map of
    every kernel of a program: a fifth of the seconds a prefill's
    program took to lower at each start (profiled by PR 38's builder)."""
    n = jnp.asarray(n, a.dtype)
    return jax.lax.div(a, n), jax.lax.rem(a, n)


def _first_key_block(i, lead, block: int, window: Optional[int]):
    """The first key tile query tile ``i`` of a row needs."""
    first = _div(lead, block)[0]
    if window is None:
        return first
    return jnp.maximum(first, _div(jnp.maximum(i * block - window + 1, 0),
                                   block)[0])


def _kernel(lead_ref, *refs, scale: float, window: Optional[int],
            block: int, steps: int):
    # q, k, v (then q2, k2 where the score is two products), o, scratch
    *ins, o_ref, m_ref, l_ref, acc_ref = refs
    q_ref, k_ref, v_ref = ins[:3]
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    lead = lead_ref[b]
    kb = _first_key_block(i, lead, block, window) + j
    live = ((i + 1) * block > lead) & (kb <= i)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def scores(q_ref, k_ref):
        return jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def tile(masked: bool):
        s = scores(q_ref, k_ref)
        if ins[3:]:
            s = s + scores(*ins[3:])
        s = s * scale
        if masked:
            qs = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            ks = kb * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            see = (ks <= qs) & (ks >= lead)
            if window is not None:
                see &= ks > qs - window
            s = jnp.where(see, s, _NEG)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # a tile wholly inside the mask: under the diagonal, behind the
    # row's first token and, with a window, younger than its far edge
    inside = (kb < i) & (kb * block >= lead)
    if window is not None:
        inside &= kb * block > (i + 1) * block - 1 - window

    @pl.when(live & inside)
    def _():
        tile(False)

    @pl.when(live & ~inside)
    def _():
        tile(True)

    @pl.when((j == steps - 1) & ((i + 1) * block > lead))
    def _():
        # ptpu: allow[unguarded-domain] — a row's sum holds exp(0) of its
        # running maximum: 1 or more once a tile has been through
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _heads(a: jax.Array, D: int, what: str) -> Tuple[int, int]:
    """``(heads, heads a group)`` of ``a [G, B, L, Hi x D]``."""
    G, Hi = a.shape[0], a.shape[-1] // D
    if Hi * D != a.shape[-1]:
        raise ValueError(f"{what}: {a.shape[-1]} lanes are not whole heads "
                         f"of {D}")
    return G * Hi, Hi


@functools.partial(jax.jit, static_argnames=("window", "scale", "block",
                                             "head_dim"))
def window_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lead: jax.Array, q2: Optional[jax.Array] = None,
                     k2: Optional[jax.Array] = None, *, scale: float,
                     window: Optional[int] = None, block: int = BLOCK,
                     head_dim: Optional[int] = None) -> jax.Array:
    """See the module's docstring. ``L`` must divide by ``block`` (which
    is held to ``L``); ``lead [B]`` int32, each under ``L``; ``head_dim``
    the ``D`` of ``q`` and ``k`` (left out: their whole last axis, one
    head a group)."""
    D = head_dim or q.shape[-1]
    B, L = q.shape[1:3]
    (Hq, Hi), (Hkv, Hik) = _heads(q, D, "q"), _heads(k, D, "k")
    if Hq % Hkv:
        raise ValueError("query heads must divide by key-value heads")
    Gv, Wv = v.shape[0], v.shape[-1]
    if Hkv % Gv or Wv % (Hkv // Gv):
        raise ValueError("v holds the key-value heads, in whole groups")
    Hiv = Hkv // Gv
    Dv = Wv // Hiv
    if (q2 is None) != (k2 is None):
        raise ValueError("a second product takes q2 and k2")
    block = min(block, L)
    if L % block:
        raise ValueError(f"{L} slots do not divide into tiles of {block}")
    n = L // block
    steps = key_steps(n, block, window)

    def first_real(i, lead_b):
        # a tile of pad slots maps to the row's first real tile: it is
        # neither fetched nor written back
        return jnp.maximum(i, _div(lead_b, block)[0])

    def queries(D, Hi):
        # head h is lanes (h % Hi) D .. of group h // Hi
        def at(b, h, i, j, lead):
            group, inside = _div(h, Hi)
            return group, b, first_real(i, lead[b]), inside
        return pl.BlockSpec((None, None, block, D), at)

    def keys(D, Hi, g):
        # query head h reads key-value head h // g
        def at(b, h, i, j, lead):
            ii = first_real(i, lead[b])
            kb = _first_key_block(ii, lead[b], block, window) + j
            group, inside = _div(_div(h, g)[0], Hi)
            return group, b, jnp.minimum(kb, ii), inside
        return pl.BlockSpec((None, None, block, D), at)

    g = Hq // Hkv
    ins = [q, k, v]
    specs = [queries(D, Hi), keys(D, Hik, g), keys(Dv, Hiv, g)]
    if q2 is not None:
        D2 = q2.shape[-1] * q2.shape[0] // Hq
        (H2, Hi2), (Hkv2, Hik2) = _heads(q2, D2, "q2"), _heads(k2, D2, "k2")
        if H2 != Hq or Hq % Hkv2:
            raise ValueError("q2 holds q's heads, k2 a divisor of them")
        ins += [q2, k2]
        specs += [queries(D2, Hi2), keys(D2, Hik2, Hq // Hkv2)]
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, block=block,
                          steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, Hq, n, steps),
            in_specs=specs, out_specs=queries(Dv, Hi),
            scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, Dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape[:-1] + (Hi * Dv,), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpreted(), name="window_attention",
    )(lead.astype(jnp.int32), *ins)


def heads_first(a: jax.Array, D: int) -> jax.Array:
    """``a [G, B, L, Hi x D]`` as ``[G x Hi, B, L, D]``: the layout with
    one head a group (a transpose: for tests and references)."""
    G, B, L, _ = a.shape
    return jnp.moveaxis(a.reshape(G, B, L, -1, D), 3, 1).reshape(
        -1, B, L, D)


def dense_attention(q, k, v, lead, q2=None, k2=None, *, scale: float,
                    window: Optional[int] = None,
                    head_dim: Optional[int] = None):
    """The same contract as dense softmax, float32, heads first
    ``[Hq, B, L, Dv]`` whatever the operands' groups: what the tests
    hold the kernel to (and small enough to read)."""
    D = head_dim or q.shape[-1]
    f32 = jnp.float32
    q, k = heads_first(q.astype(f32), D), heads_first(k.astype(f32), D)
    Hq, B, L, _ = q.shape
    v = heads_first(v.astype(f32), v.shape[-1] * v.shape[0] // k.shape[0])
    if q2 is not None:  # one score over the concatenated operands
        D2 = q2.shape[-1] * q2.shape[0] // Hq
        k2 = heads_first(k2.astype(f32), D2)
        q = jnp.concatenate([q, heads_first(q2.astype(f32), D2)], axis=-1)
        k = jnp.concatenate(
            [k, jnp.repeat(k2, k.shape[0] // k2.shape[0], axis=0)], axis=-1)
    g = Hq // k.shape[0]
    kk, vv = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    s = jnp.einsum("hbqd,hbkd->hbqk", q, kk, precision="highest") * scale
    at = jnp.arange(L)
    see = (at[None, :] <= at[:, None])[None] \
        & (at[None, None, :] >= lead[:, None, None])
    if window is not None:
        see &= (at[None, :] > at[:, None] - window)[None]
    p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hbqk,hbkd->hbqd", p, vv, precision="highest")
