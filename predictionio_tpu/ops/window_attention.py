"""Blockwise causal attention over right-aligned rows, with an optional
sliding window: the generative prefill's attention (``models/decoder.py``).

``q [Hq, B, L, D]``, ``k [Hkv, B, L, D]``, ``v [Hkv, B, L, Dv]`` (heads
first, as a projection by head makes them; ``Dv`` is ``D`` in the
grouped-query families and 128 beside a ``D`` of 192 under latent
attention) hold ``B`` rows of up to ``L`` tokens,
RIGHT-aligned: row ``b``'s tokens lie in slots
``lead[b] .. L-1`` (the layout the decode's cache keeps), so a slot's
index is its position plus ``lead[b]`` and causal order is slot order.
Query slot ``i`` sees key slots ``max(lead, i - window + 1) .. i``
(``window`` ``None``: all from ``lead``). Query head ``h`` reads
key-value head ``h // (Hq / Hkv)`` through the block index, so ``k`` and
``v`` are never repeated in memory. Returns ``o [Hq, B, L, Dv]`` in
``q``'s dtype; slots before ``lead[b]`` hold nothing defined (blocks of
pad slots are not computed and not written). What ``k`` and ``v`` hold
before ``lead[b]`` weighs exactly 0 as long as it is finite.

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
from typing import Optional

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
                    "(window_attention, touched_experts, hyper_mix) run "
                    "in Pallas' interpreter", jax.default_backend())
    return off_chip


def key_steps(n_blocks: int, block: int, window: Optional[int]) -> int:
    """Key tiles a query tile can need: all up to its own (causal), or
    those a window spans."""
    if window is None:
        return n_blocks
    return min(n_blocks, max(
        i - max(i * block - window + 1, 0) // block + 1
        for i in range(n_blocks)))


def _first_key_block(i, lead, block: int, window: Optional[int]):
    """The first key tile query tile ``i`` of a row needs."""
    first = lead // block
    if window is None:
        return first
    return jnp.maximum(first, jnp.maximum(i * block - window + 1, 0)
                       // block)


def _kernel(lead_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, window: Optional[int], block: int, steps: int):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    lead = lead_ref[b]
    kb = _first_key_block(i, lead, block, window) + j
    live = ((i + 1) * block > lead) & (kb <= i)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(masked: bool):
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
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


@functools.partial(jax.jit, static_argnames=("window", "scale", "block"))
def window_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lead: jax.Array, *, scale: float,
                     window: Optional[int] = None,
                     block: int = BLOCK) -> jax.Array:
    """See the module's docstring. ``L`` must divide by ``block`` (which
    is held to ``L``); ``lead [B]`` int32, each under ``L``."""
    Hq, B, L, D = q.shape
    Hkv, Dv = k.shape[0], v.shape[-1]
    if Hq % Hkv:
        raise ValueError("query heads must divide by key-value heads")
    block = min(block, L)
    if L % block:
        raise ValueError(f"{L} slots do not divide into tiles of {block}")
    g, n = Hq // Hkv, L // block
    steps = key_steps(n, block, window)

    def first_real(i, lead_b):
        # a tile of pad slots maps to the row's first real tile: it is
        # neither fetched nor written back
        return jnp.maximum(i, lead_b // block)

    def q_map(b, h, i, j, lead):
        return h, b, first_real(i, lead[b]), 0

    def kv_map(b, h, i, j, lead):
        ii = first_real(i, lead[b])
        kb = _first_key_block(ii, lead[b], block, window) + j
        return h // g, b, jnp.minimum(kb, ii), 0

    tile, v_tile = (None, None, block, D), (None, None, block, Dv)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, block=block,
                          steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, Hq, n, steps),
            in_specs=[pl.BlockSpec(tile, q_map), pl.BlockSpec(tile, kv_map),
                      pl.BlockSpec(v_tile, kv_map)],
            out_specs=pl.BlockSpec(v_tile, q_map),
            scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, Dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape[:-1] + (Dv,), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpreted(), name="window_attention",
    )(lead.astype(jnp.int32), q, k, v)


def dense_attention(q, k, v, lead, *, scale: float,
                    window: Optional[int] = None):
    """The same contract as dense softmax, float32: what the tests hold
    the kernel to (and small enough to read)."""
    Hq, B, L, D = q.shape
    g = Hq // k.shape[0]
    f32 = jnp.float32
    kk = jnp.repeat(k.astype(f32), g, axis=0)
    vv = jnp.repeat(v.astype(f32), g, axis=0)
    s = jnp.einsum("hbqd,hbkd->hbqk", q.astype(f32), kk,
                   precision="highest") * scale
    at = jnp.arange(L)
    see = (at[None, :] <= at[:, None])[None] \
        & (at[None, None, :] >= lead[:, None, None])
    if window is not None:
        see &= (at[None, :] > at[:, None] - window)[None]
    p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hbqk,hbkd->hbqd", p, vv, precision="highest")
