"""Sparse experts: sigmoid routing over ALL experts, and the product
over the experts this chip HOLDS.

The routing is the published one of the ``lfm2_moe`` family (and of the
sigmoid-routed families before it): scores ``s = sigmoid(W_g z)`` over
every expert; the top ``k`` are SELECTED by ``s + b`` with a per-expert
bias ``b`` that balances load, and WEIGHTED by ``s`` without it;
``norm_topk_prob`` divides the weights by their sum (plus ``1e-6``).
No token is dropped and there is no capacity limit.

An expert is one of two blocks, told by the weights it is given: the
gated one, ``W2 (silu(W1 x) * W3 x)`` (three matrices), or the plain
one with a squared ReLU, ``W2 relu(W1 x)^2`` (two matrices, ``w3`` is
``None``: the ``nemotron_h`` family's, whose rows are the family's
LATENTS, narrower than the stream; the projections into and out of the
latent belong to the layer and are the caller's, once a layer).

The product (:func:`expert_product`) has three forms and picks among
them from shapes it can see (:func:`product_form`): the tokens ``T``,
the experts a token ``k``, the experts held ``E_held`` and the experts
routed over ``E``. Under a share only ``E_held / E`` of a step's ``T x
k`` assignments can be expected to land here, and that many are what
the rule weighs (16 rows x 22 over 512 experts of which 128 are held:
88 assignments here, which touch about 64 of the 128). MANY tokens
(a prefill, over ``DENSE_MAX_ROWS``): the (token,
expert) assignments are sorted by expert and each weight takes one
grouped matrix product (``jax.lax.ragged_dot``: a Mosaic grouped-matmul
kernel on a TPU, ``ragged-dot`` in its trace, and a native op on the
CPU), so the work is that of the assignments made and not of experts x
tokens. The third product's rows ``y [T*k, H]`` float32 stay where the
sort put them: the combine walks the TOKENS, gathers each token's ``k``
rows through the inverse permutation and sums them under the routing
weights and the mask as routed (``[T, k]``), so ``y`` is read once and
no other array of its size is written (:func:`_combine`). A stream of
more than ``BLOCK_ASSIGNMENTS`` assignments goes through all of that in
equal blocks of tokens, one after the other (``lax.map``), so the
temporaries are a block's whatever the stream. FEW tokens (a decode
step, ``DENSE_MAX_ROWS`` or under) stream expert weights past the rows,
and the question is WHICH experts' weights. Where the step's
assignments here outnumber the held experts (``TOUCHED_REACH x T x k x
E_held / E > E_held``: 64 rows x 4 over 32 experts touch 31.5 of
them), every held
expert is computed for every token in one
batched product and the routing weights, zero where an expert was not
selected, do the selecting (:func:`_every_expert`): each expert's
weights are read once either way, and the batched product reads them at
14.2 ms a step of 64 rows where the sorted one took 24.5 (my chip run,
PR 27). Where they do not (16 rows x 8 over 256 experts touch 94),
only the experts that at least one row
selected are fetched and multiplied (:func:`_touched_experts`: one Pallas
kernel, ``touched_experts`` in a device trace, whose grid walks the
ascending list of distinct selected experts through a prefetched
index; an expert nobody selected is never read, where the batched
product read it and multiplied its output by zero: 0.79 ms a layer
where the batched product took 2.24, my chip run, PR 33).
An assignment to an expert that is not held
here (``held``: the chip's share of an expert-parallel deployment,
model-configs guide section 4) or from a slot that is padding
(``valid``) joins no group: it is sorted behind the last group and its
rows are never computed. What the absent experts would have added is
left out; four shares of a layer add up to the whole layer
(``tests/test_decoder.py``).

Off the TPU the kernel runs in Pallas' interpreter (tests, rehearsals).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .window_attention import _interpreted

NORM_EPS = 1e-6  # the family's constant in the top-k normalisation
#: tokens up to which expert weights are streamed past all the tokens
#: (the two few-token forms). Reading one expert's weights (2048 x 1792
#: x 3) takes 27 us at the v5e's 819 GB/s and computing it for one more
#: token 0.11 us at its 197 TFLOP/s: under about 240 tokens the weights'
#: streaming hides the products nobody selected. Half of that, for an
#: MXU that is not at its peak at these heights.
DENSE_MAX_ROWS = 128
#: a few-token step reads only the experts its rows selected where
#: this many times its ``T x k`` assignments do not outnumber the held
#: experts. One layer of 256 experts of 2048 x 512 x 3 bfloat16, device
#: ms touched / every (my chip run, PR 33): 16 rows x 8 with 94 distinct
#: 0.79 / 2.24 (60: 0.52, all 128: 1.07); 32 x 8 with 162 (what
#: uniform routing touches at ``T x k = E_held``) 1.37 / 2.14; 64 x 8
#: with 221 1.85 / 2.14. Both forms stream an expert in 8.4-8.7 us, so
#: the kernel wins by the share of experts nobody selected: 37 % or more
#: at 1. An expert in 7 tiles of ``F`` (32 of 2048 x 1792 x 3): 8 rows
#: x 4 with 20 distinct 0.61 / 0.94, with all 32 0.94 / 0.94. Past 1 the
#: most it can win is the third or less of the experts that uniform
#: routing leaves untouched, and where the rows touch them all (64 x 4
#: over 32: 0.95 / 0.95) nothing.
TOUCHED_REACH = 1
#: bytes of ONE grid step's three weight blocks in the touched-experts
#: kernel (the pipeline holds two steps' worth): a whole expert where
#: it fits (2048 x 512 x 3 bfloat16 are 6.3 MB, three contiguous reads),
#: else equal tiles of ``F`` in multiples of 128 lanes
EXPERT_BLOCK_BYTES = 8 << 20
#: assignments (tokens x experts a token) sorted and multiplied at once.
#: Per assignment the many-token form holds a gathered row and an output
#: row of ``H`` (bfloat16 and float32) and two of ``F``: 1.6 GB at
#: ``H`` 2048, ``F`` 512 and this many, where a 65,536-slot stream at 8
#: experts a token would hold 6.4 GB whole. 16,384 tokens at 8, 32,768
#: at 4: every stream the ``lfm2_moe`` cell runs is one block.
BLOCK_ASSIGNMENTS = 131072


def equal_parts(total: int, size: int, limit: int) -> int:
    """The fewest equal parts of ``total`` with ``part x size`` at
    ``limit`` or under (1 where the whole is)."""
    return next(n for n in range(-(-total * size // limit), total + 1)
                if total % n == 0)


def route(z: jax.Array, w_gate: jax.Array, bias: Optional[jax.Array], *,
          top_k: int, norm_topk: bool = True, scale: float = 1.0
          ) -> Tuple[jax.Array, jax.Array]:
    """``(selected [T, k] int32, weights [T, k] float32)`` for tokens
    ``z [T, H]`` over all ``E`` experts of ``w_gate [H, E]``. The scores,
    the sums and the product itself are float32 (``highest``: the gate
    is 32 columns wide and decides WHICH 22 MB are read next, so it is
    not the place to round)."""
    logits = jnp.dot(z.astype(jnp.float32), w_gate.astype(jnp.float32),
                     precision="highest",
                     preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    pick = s if bias is None else s + bias.astype(jnp.float32)
    _, sel = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_EPS)
    return sel.astype(jnp.int32), w * scale


def local_index(sel: jax.Array, n_experts: int,
                held: Optional[Sequence[int]]) -> Tuple[jax.Array, int]:
    """Expert ids as indices into the held weights; ``n_held`` (one past
    the last group) for an expert that lives on another chip."""
    if held is None:
        return sel, n_experts
    # a constant of the program (``held`` is static): built on the device
    # by a scatter, the table crashed the v5e compiler's fusion pass in
    # the decode's loop (scatter_emitter.cc, PR 48)
    table = np.full((n_experts,), len(held), np.int32)
    table[list(held)] = np.arange(len(held))
    return jnp.asarray(table)[sel], len(held)


def expert_load(sel: jax.Array, n_experts: int,
                valid: Optional[jax.Array] = None) -> jax.Array:
    """Tokens per expert ``[E]`` int32 over ALL experts (pad slots
    count for none): what the counters read."""
    flat = sel if valid is None else jnp.where(valid[:, None], sel,
                                               n_experts)
    # compared and summed, not scattered: a scatter-add of T x k ones
    # took 1.1 ms a layer at 262,144 assignments (my chip run, PR 32)
    return jnp.sum(flat.reshape(-1, 1) == jnp.arange(
        n_experts, dtype=flat.dtype), axis=0, dtype=jnp.int32)


SORTED, TOUCHED, EVERY = "sorted_groups", "touched_experts", "every_expert"


def product_form(tokens: int, top_k: int, n_held: int,
                 n_experts: Optional[int] = None) -> str:
    """Which of the three forms :func:`expert_product` takes for
    ``tokens`` rows routed ``top_k`` ways over ``n_experts`` experts of
    which ``n_held`` are held here (``None``: all of them): shapes alone
    decide (the module's docstring says why). The assignments that can
    be expected HERE are ``tokens x top_k x n_held / n_experts``."""
    if tokens > DENSE_MAX_ROWS:
        return SORTED
    n_experts = n_held if n_experts is None else n_experts
    # T k E_held / E <= E_held, in whole numbers
    if TOUCHED_REACH * tokens * top_k * n_held <= n_held * n_experts:
        return TOUCHED
    return EVERY


def expert_product(x: jax.Array, sel: jax.Array, wts: jax.Array,
                   w1: jax.Array, w3: Optional[jax.Array], w2: jax.Array, *,
                   n_experts: int, held: Optional[Sequence[int]] = None,
                   valid: Optional[jax.Array] = None) -> jax.Array:
    """``sum_e w_e W2_e (silu(W1_e x) * W3_e x)`` over the held experts
    (``w3`` ``None``: ``sum_e w_e W2_e relu(W1_e x)^2``), ``[T, H]``
    float32. ``x [T, H]`` in the weights' dtype, ``sel`` / ``wts [T,
    k]`` from :func:`route`, ``w1`` / ``w3 [E_held, H, F]``, ``w2
    [E_held, F, H]``; products accumulate in float32."""
    T, k = sel.shape
    local, n_held = local_index(sel, n_experts, held)
    if valid is not None:
        local = jnp.where(valid[:, None], local, n_held)
    form = {SORTED: _sorted_groups, TOUCHED: _touched_experts,
            EVERY: _every_expert}[product_form(T, k, n_held, n_experts)]
    return form(x, local, wts, w1, w3, w2)


def _hidden(a, gate=None):
    """An expert's hidden activations from its first product, float32:
    ``silu(a) * gate()``, or ``relu(a)^2`` where the expert has no gate
    matrix. ``gate`` is CALLED for the gate matrix's product after
    ``silu(a)`` is written: the statement order the gated experts had
    before experts of two matrices (PR 48), which is the order a Pallas
    kernel's body is scheduled in (the traced programs of the gated
    families are the parent's, statement for statement)."""
    if gate is None:
        return jnp.square(jax.nn.relu(a))
    return jax.nn.silu(a) * gate()


def _every_expert(x, local, wts, w1, w3, w2):
    """Few tokens: one batched product over the held experts, weighted
    by the dense ``[T, E_held]`` routing matrix (an assignment to an
    absent expert or from a pad slot has index ``E_held``: no column)."""
    f32 = jnp.float32
    dense = jnp.sum(jax.nn.one_hot(local, w1.shape[0], dtype=f32)
                    * wts[..., None], axis=1)

    def into(w):
        return jnp.einsum("th,ehf->etf", x, w, preferred_element_type=f32)

    h = _hidden(into(w1), None if w3 is None else lambda: into(w3))
    y = jnp.einsum("etf,efh->eth", h.astype(x.dtype), w2,
                   preferred_element_type=f32)
    # float32 x float32: at the default precision the MXU would round
    # the routing weights and the experts' outputs to bfloat16
    return jnp.einsum("te,eth->th", dense, y, precision="highest")


def touched_list(local: jax.Array, n_held: int, length: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """``(ids [length] int32, n)``: the ``n`` DISTINCT held experts of
    ``local`` (``n_held`` there is no expert) in ascending order, the
    list padded to its static ``length`` with its last entry (0 where
    ``n`` is 0), so that a padded step asks for the block already held.
    Compared and summed, as :func:`expert_load` (whose counts say which
    experts were hit): entry ``g`` is the number of experts with ``g``
    or fewer touched ones up to them."""
    upto = jnp.cumsum(expert_load(local, n_held) > 0, dtype=jnp.int32)
    n = upto[-1]
    at = jnp.minimum(jnp.arange(length, dtype=jnp.int32),
                     jnp.maximum(n - 1, 0))
    ids = jnp.sum(upto[None, :] <= at[:, None], axis=1, dtype=jnp.int32)
    return jnp.where(n > 0, ids, 0), n


def _f_tiles(H: int, F: int, itemsize: int, matrices: int = 3) -> int:
    """The fewest equal tiles of ``F`` (the whole, or multiples of 128
    lanes) that keep a step's weight blocks (``matrices`` of them: three
    of a gated expert, two of a plain one) at ``EXPERT_BLOCK_BYTES`` or
    under; the finest there is where none does."""
    ways = [n for n in range(1, F + 1)
            if F % n == 0 and (n == 1 or F // n % 128 == 0)]
    return next((n for n in ways
                 if matrices * H * (F // n) * itemsize
                 <= EXPERT_BLOCK_BYTES), ways[-1])


def _touched_kernel(ids_ref, n_ref, x_ref, dense_ref, *refs):
    """``refs``: the expert's first matrices (``w1`` and, where it is
    gated, ``w3``), its ``w2`` and the output block."""
    *into_refs, w2_ref, o_ref = refs
    g, j = pl.program_id(0), pl.program_id(1)

    @pl.when((g == 0) & (j == 0))
    def _():  # whether or not any expert follows
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(g < n_ref[0])
    def _():
        f32 = jnp.float32
        x = x_ref[...]

        def into(w_ref):
            return jnp.dot(x, w_ref[...], preferred_element_type=f32)

        first, *gate = into_refs
        h = _hidden(into(first), *(functools.partial(into, w) for w in gate))
        y = jnp.dot(h.astype(x.dtype), w2_ref[...],
                    preferred_element_type=f32)
        # the expert's column of the routing matrix, float32 on the VPU
        dense = dense_ref[...]
        col = jax.lax.broadcasted_iota(jnp.int32, dense.shape, 1)
        o_ref[...] += y * jnp.sum(jnp.where(col == ids_ref[g], dense, 0.0),
                                  axis=1, keepdims=True)


def _touched_experts(x, local, wts, w1, w3, w2):
    """Few tokens that cannot reach most experts: one Pallas kernel
    (``touched_experts`` in a device trace) over the grid ``(G, F /
    tile)``, ``G = min(E_held, T x k)``. Step ``g`` under the count of
    distinct selected experts fetches expert ``ids[g]``'s weights (the
    block index comes from the prefetched list), multiplies the resident
    rows through it as :func:`_every_expert` does (operands in the
    weights' dtype, float32 accumulation, ``silu(a) * b`` rounded once)
    and adds the output, times the expert's float32 column of the dense
    routing matrix, to the float32 ``[T, H]`` output block, which stays
    in VMEM from the first step (zeroed there) to the last (written
    once). A later step maps to the block already held: no DMA, no
    product. Only the float32 order of the sum over experts differs
    from :func:`_every_expert`. An expert of two matrices (``w3``
    ``None``) goes through the same grid with two weight blocks a
    step."""
    T, k = local.shape
    n_held, H, F = w1.shape
    f32 = jnp.float32
    dense = jnp.sum(jax.nn.one_hot(local, n_held, dtype=f32)
                    * wts[..., None], axis=1)
    G = min(n_held, T * k)
    ids, n = touched_list(local, n_held, G)
    rows = -(-T // 16) * 16  # whole sublane tiles of either dtype
    x = jnp.pad(x, ((0, rows - T), (0, 0)))
    dense = jnp.pad(dense, ((0, rows - T), (0, 0)))
    into = (w1,) if w3 is None else (w1, w3)
    nf = _f_tiles(H, F, w1.dtype.itemsize, len(into) + 1)
    tile = F // nf

    def whole(g, j, ids, n):
        return 0, 0

    def f_tile(g, j, n):
        # behind the list, the LAST block fetched: (ids[n - 1], nf - 1)
        return jnp.where(g < n[0], j, nf - 1)

    def in_map(g, j, ids, n):
        return ids[g], 0, f_tile(g, j, n)

    def out_map(g, j, ids, n):
        return ids[g], f_tile(g, j, n), 0

    out = pl.pallas_call(
        _touched_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(G, nf),
            in_specs=[pl.BlockSpec((rows, H), whole),
                      pl.BlockSpec((rows, n_held), whole),
                      *(pl.BlockSpec((None, H, tile), in_map) for _ in into),
                      pl.BlockSpec((None, tile, H), out_map)],
            out_specs=pl.BlockSpec((rows, H), whole)),
        out_shape=jax.ShapeDtypeStruct((rows, H), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * EXPERT_BLOCK_BYTES + (16 << 20)),
        interpret=_interpreted(), name="touched_experts",
    )(ids, n.reshape(1), x, dense, *into, w2)
    return out[:T]


def _sorted_groups(x, local, wts, w1, w3, w2):
    """Many tokens, in as few equal blocks of tokens as keep a block's
    assignments at ``BLOCK_ASSIGNMENTS`` or under; a token's experts are
    all in its block, so the blocks' outputs are the stream's, one
    behind the other."""
    T, k = local.shape
    n = equal_parts(T, k, BLOCK_ASSIGNMENTS)
    if n == 1:
        return _sorted_block(x, local, wts, w1, w3, w2)
    out = jax.lax.map(
        lambda a: _sorted_block(*a, w1, w3, w2),
        (x.reshape(n, T // n, -1), local.reshape(n, T // n, k),
         wts.reshape(n, T // n, k)))
    return out.reshape(T, -1)


def _sorted_block(x, local, wts, w1, w3, w2):
    """Assignments sorted by expert, one grouped product per weight;
    index ``E_held`` sorts behind the last group. Every index below is
    in range by construction and the gathers say so: a fill-mode gather
    pays a select over its whole output."""
    T, k = local.shape
    n_held = w1.shape[0]
    keys, order = jax.lax.sort_key_val(
        local.reshape(-1), jnp.arange(T * k, dtype=jnp.int32))
    # group sizes off the sorted keys' boundaries (no scatter-add)
    ends = jnp.sum(keys[None, :] <= jnp.arange(
        n_held, dtype=keys.dtype)[:, None], axis=1, dtype=jnp.int32)
    sizes = jnp.diff(ends, prepend=0)
    xs = x.at[order // k].get(mode="promise_in_bounds")
    f32 = jnp.float32

    def into(w):
        return jax.lax.ragged_dot(xs, w, sizes, preferred_element_type=f32)

    h = _hidden(into(w1), None if w3 is None else lambda: into(w3))
    y = jax.lax.ragged_dot(h.astype(x.dtype), w2, sizes,
                           preferred_element_type=f32)
    # the inverse permutation: assignment (t, j) is row back[t, j] of y
    back = jnp.argsort(order).reshape(T, k)
    return _combine(y, back, local < n_held, wts)


def _combine(y, back, live, wts):
    """``out[t] = sum_j where(live[t, j], wts[t, j] * y[back[t, j]], 0)``
    float32, in TOKEN order: ``y [T*k, H]`` is read once, row by row,
    by ``k`` gathers of ``[T, H]`` that the weighted sum consumes; the
    mask and the weights are the ``[T, k]`` arrays as routed. Rows of
    ``y`` behind the last group are never written, and 0 x garbage is
    not 0: the mask is applied to the gathered row, not to its weight."""
    def term(j):
        rows = y.at[back[:, j]].get(mode="promise_in_bounds",
                                    unique_indices=True)
        return jnp.where(live[:, j:j + 1], wts[:, j:j + 1] * rows, 0.0)

    out = term(0)
    for j in range(1, back.shape[1]):
        out = out + term(j)
    return out
