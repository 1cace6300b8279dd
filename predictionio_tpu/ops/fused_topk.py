"""Fused gather→score→top-k Pallas kernel — the serving-side HBM attack.

The batched serving lane (``models/als.py::_serve_topk``) materializes
the full ``[B, I]`` score matrix in HBM before ``lax.top_k`` reduces it
to ``[B, k]`` — at ML-20M scale that is ~230 MB written and read back
per 2048-query dispatch for a result that is 3 orders of magnitude
smaller. This kernel is the serving twin of ``ops/fused_gram.py``
(PR 7): stream, don't materialize.

- per query block, the block's user indices hop from their VMEM block
  into an SMEM tile whose scalar reads drive per-row DMAs pulling user
  rows from the HBM-resident table straight into a ``[block_q, r]``
  VMEM tile (int8/bf16 on the wire for row-quantized serving tables —
  dequantized AFTER the DMA with f32 accumulation, the Tensor-Casting
  precision co-design, arXiv 2010.13100);
- the item table streams through a double-buffered ``[2, chunk, r]``
  VMEM tile — chunk c+1's DMA is in flight while the MXU contracts
  ``[block_q, r] × [r, chunk]`` for chunk c (the fused_gram idiom);
- each chunk's scores merge into an on-chip running top-k
  (``[block_q, k]`` carried through the chunk loop), so the only HBM
  writes are the final ``[B, k]`` ids+scores — the ``[B, I]`` score
  matrix never exists.

Per scored element the HBM traffic drops from ``r·4 + 8`` B (table read
plus score write+readback) to ``r·wire_bytes`` B — ~3× less on the f32
wire and ~12× on int8 rows, which is what moves the batched lane off
the HBM roof (``benchmarks/roofline_probe.py`` PROBE_SERVE measures
where the bound lands).

Entry points mirror fused_gram's contract:

- :func:`fused_topk` — the kernel (``interpret=True`` runs anywhere);
- :func:`fused_topk_dispatch` — the compiled kernel on TPU (a kernel
  the compiler refuses RAISES with the compiler's message — nothing
  stands in for it), the interpret-mode kernel elsewhere (explicit
  ``serving topk="fused"`` on CPU is a debugging run);
- :func:`fused_topk_reference` — the jnp mirror the parity tests hold
  the kernel against (never a fallback);
- :func:`fused_topk_refusal` — compile probe at the shapes about to
  run; returns the compiler's message when it refuses.

On the installed JAX (0.9.0) the kernel does NOT lower for TPU at any
shape: ``lax.top_k`` has no Pallas TPU lowering and the ``(1, block_q)``
index block is rejected for every batch above 8. It needs an in-kernel
top-k that Mosaic lowers; until then no autotune entry names it, and an
explicit ``--serving-topk fused`` fails the deploy on a TPU backend.

Routed through ``models/als.py::_device_topk`` (single + replicated
lanes + pinned hot tier) and ``_sharded_rank_fn`` (per-shard local
top-k with a global ``base`` id offset), picked by the
``gram_autotune.best_topk_mode`` table. See docs/kernels.md for the
VMEM budget math (audited statically by ``ptpu check`` vmem-overbudget
and asserted at trace time below).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _probe

#: query rows scored per grid step — bounds the user tile and the
#: running top-k carry; the item-chunk sweep, not the block size, sets
#: the pipeline depth
_BLOCK_Q = 8

#: item rows per double-buffer fill. Bounds the VMEM working set at
#: ``2·chunk·r·wire_bytes`` (512 KiB at r=128 f32, 128 KiB on the int8
#: wire) however large the catalog grows.
_ITEM_CHUNK = 512

#: largest k the on-chip merge carries. Past this the einsum path wins
#: anyway (the [B, I] matrix amortizes over more extracted rows) and
#: the merge's [block_q, k+chunk] top_k stops being cheap — the
#: dispatcher falls back instead of scaling the carry.
TOPK_MAX_K = 128


def fused_topk_vmem_bytes(rank: int, k: int, wire_bytes: int = 4,
                          block_q: int = _BLOCK_Q,
                          chunk: int = _ITEM_CHUNK) -> int:
    """VMEM bytes the kernel holds live per core (docs/kernels.md):
    the double-buffered item tiles + scale rows, the user tile, the
    staged scale/index blocks, the running top-k carry and the merge
    temp, and the output tile."""
    item = 2 * chunk * rank * wire_bytes       # double-buffered chunks
    iscale = 2 * chunk * 4                     # per-chunk scale rows
    ubuf = block_q * rank * wire_bytes         # gathered user rows
    blocks = block_q * 4 * 2                   # idx + uscale blocks
    carry = block_q * k * (4 + 4)              # running top-k s+ids
    merge = block_q * (k + chunk) * (4 + 4)    # concat temp for top_k
    out = block_q * k * (4 + 4)                # output tile
    return item + iscale + ubuf + blocks + carry + merge + out


def _fused_topk_kernel(n_chunks: int, chunk: int, k: int, n_items: int,
                       has_scale: bool, *refs):
    """One ``[block_q]`` query block: gather the block's user rows by
    per-row DMA (indices staged VMEM→SMEM so scalar reads drive the
    copies), then sweep the item table chunk by chunk — chunk c+1's
    block DMA in flight while the MXU scores chunk c — merging each
    chunk's ``[block_q, chunk]`` scores into the on-chip running
    top-k. Only the final ``[block_q, k]`` ids+scores leave the
    core."""
    if has_scale:
        (idx_ref, us_ref, base_ref, utab_ref, itab_ref, isc_ref,
         outs_ref, outi_ref, ubuf, ibuf, vbuf, sbuf,
         usem, isem, vsems, ssems) = refs
    else:
        (idx_ref, base_ref, utab_ref, itab_ref,
         outs_ref, outi_ref, ubuf, ibuf, vbuf,
         usem, isem, vsems) = refs
        us_ref = isc_ref = sbuf = ssems = None
    block_q = ubuf.shape[0]

    def issue_chunk(c, slot):
        pltpu.make_async_copy(
            itab_ref.at[pl.ds(c * chunk, chunk), :],
            vbuf.at[slot], vsems.at[slot]).start()
        if has_scale:
            pltpu.make_async_copy(
                isc_ref.at[pl.ds(c, 1), :],
                sbuf.at[slot], ssems.at[slot]).start()

    def wait_chunk(slot):
        pltpu.make_async_copy(
            itab_ref.at[pl.ds(0, chunk), :],
            vbuf.at[slot], vsems.at[slot]).wait()
        if has_scale:
            pltpu.make_async_copy(
                isc_ref.at[pl.ds(0, 1), :],
                sbuf.at[slot], ssems.at[slot]).wait()

    # stage this block's indices into scalar memory: row DMAs need
    # scalar source addresses
    icopy = pltpu.make_async_copy(idx_ref.at[pl.ds(0, 1), :],
                                  ibuf.at[pl.ds(0, 1), :], isem)
    icopy.start()
    icopy.wait()

    # the user-row gather DMAs ride alongside the first item chunk's
    # block DMA — both in flight before anything waits
    issue_chunk(0, 0)

    def issue_row(q, c):
        pltpu.make_async_copy(
            utab_ref.at[pl.ds(ibuf[0, q], 1), :],
            ubuf.at[pl.ds(q, 1), :], usem).start()
        return c

    jax.lax.fori_loop(0, block_q, issue_row, 0, unroll=False)

    def wait_row(q, c):
        pltpu.make_async_copy(
            utab_ref.at[pl.ds(0, 1), :],
            ubuf.at[pl.ds(q, 1), :], usem).wait()
        return c

    jax.lax.fori_loop(0, block_q, wait_row, 0, unroll=False)

    # dequantize AFTER the wire: int8/bf16 rows upcast in VMEM and
    # every contraction accumulates f32 (preferred_element_type)
    q_rows = ubuf[:].astype(jnp.float32)                # [block_q, r]
    if has_scale:
        q_rows = q_rows * us_ref[0][:, None]
    base = base_ref[0, 0]

    neg = jnp.full((block_q, k), -jnp.inf, dtype=jnp.float32)
    zero_ids = jnp.zeros((block_q, k), dtype=jnp.int32)

    def step(c, carry):
        acc_s, acc_i = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            issue_chunk(c + 1, jax.lax.rem(c + 1, 2))

        wait_chunk(slot)
        v = vbuf[slot].astype(jnp.float32)              # [chunk, r]
        s = jax.lax.dot_general(
            q_rows, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [block_q, chunk]
        if has_scale:
            s = s * sbuf[slot][0][None, :]
        gid = (base + c * chunk
               + jax.lax.broadcasted_iota(jnp.int32, (block_q, chunk),
                                          1))
        s = jnp.where(gid < n_items, s, -jnp.inf)
        # streaming merge: earlier chunks sit first in the concat, so
        # lax.top_k's prefer-lower-position tie rule reproduces the
        # reference's prefer-lower-id semantics globally
        cat_s = jnp.concatenate([acc_s, s], axis=1)
        cat_i = jnp.concatenate([acc_i, gid], axis=1)
        top_s, pos = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, pos, axis=1)
        return top_s, top_i

    acc_s, acc_i = jax.lax.fori_loop(0, n_chunks, step,
                                     (neg, zero_ids), unroll=False)
    outs_ref[:] = acc_s
    outi_ref[:] = acc_i


def _pad_rows_to(x: jax.Array, to: int, fill=0) -> jax.Array:
    n = x.shape[0]
    if n == to:
        return x
    pad = [(0, to - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


def _pow2_ceil(n: int, lo: int = 1) -> int:
    p = lo
    while p < n:
        p <<= 1
    return p


@functools.partial(jax.jit, static_argnames=("k", "n_items", "block_q",
                                             "chunk", "interpret"))
def fused_topk(user_table: jax.Array, idx: jax.Array,
               item_table: jax.Array,
               user_scale: Optional[jax.Array] = None,
               item_scale: Optional[jax.Array] = None,
               base: Optional[jax.Array] = None, *, k: int,
               n_items: int, block_q: int = _BLOCK_Q,
               chunk: Optional[int] = None,
               interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """Fused gather→score→top-k from HBM-resident tables: returns
    ``(scores [B, k] f32, ids [B, k] int32)`` for
    ``scores[b] = top_k((user_table[idx[b]]·u_scale) @
    (item_table·i_scale)ᵀ)`` with ids offset by ``base`` (the sharded
    ranker's global-id origin; padding items — global id ≥ n_items —
    are masked to -inf exactly like ``_serve_topk``).

    ``user_scale``/``item_scale`` are the per-row f32 scales of
    int8-quantized tables (both or neither — bf16/f32 tables carry
    none). B pads to the block multiple and the catalog to the chunk
    multiple internally; ragged tails are the normal case."""
    assert (user_scale is None) == (item_scale is None), \
        "int8 tables quantize both sides (scales come in pairs)"
    B = idx.shape[0]
    m, r = user_table.shape
    Ip = item_table.shape[0]
    assert 1 <= k <= TOPK_MAX_K, \
        f"fused_topk carries k <= {TOPK_MAX_K} on chip, got {k}"
    c = min(chunk or _ITEM_CHUNK, _pow2_ceil(max(Ip, 8)))
    c = max(c, k)  # the merge width k+chunk must cover k candidates
    Ipad = -(-Ip // c) * c
    n_chunks = Ipad // c
    Bp = max(-(-B // block_q) * block_q, block_q)
    wire = item_table.dtype.itemsize
    # `ptpu check` (vmem-overbudget) audits this statically; assert the
    # same bound at trace time so an exotic (rank, k, chunk) override
    # fails loudly on the host instead of OOMing VMEM mid-serve
    assert fused_topk_vmem_bytes(r, k, wire, block_q, c) \
        < 16 * 1024 * 1024, \
        f"fused_topk VMEM working set exceeds the ~16 MiB/core " \
        f"budget at rank {r}, k {k}, chunk {c} (docs/kernels.md)"

    idxp = _pad_rows_to(idx.astype(jnp.int32), Bp).reshape(
        Bp // block_q, block_q)
    itab = _pad_rows_to(item_table, Ipad)
    has_scale = item_scale is not None
    inputs = [idxp]
    in_specs = [pl.BlockSpec((1, block_q), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)]
    if has_scale:
        # the user-row scales ride as a pre-gathered [B]-sized block —
        # a [B] fetch from the [m, 1] scale vector, nothing like the
        # [m, r] table the row DMAs exist to avoid
        # ptpu: allow[materialized-gather] — [B]-bounded scale fetch
        us = user_scale.reshape(-1)[idxp.reshape(-1)].astype(
            jnp.float32)
        inputs.append(us.reshape(Bp // block_q, block_q))
        in_specs.append(pl.BlockSpec((1, block_q), lambda i: (i, 0),
                                     memory_space=pltpu.VMEM))
    # ptpu: allow[recompile-hazard] — `base is None` is pytree
    # STRUCTURE, not a traced value: jit already specializes on the
    # argument's presence, so this branch can never retrace per value
    if base is None:
        base_arr = jnp.zeros((1, 1), jnp.int32)
    else:
        base_arr = jnp.asarray(base).astype(jnp.int32).reshape(1, 1)
    inputs.append(base_arr)
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    # both factor tables STAY in HBM — user rows are DMA'd by index,
    # item chunks stream through the double buffer; a VMEM-resident
    # BlockSpec would cap the catalog at the ~16MB core budget
    inputs.append(user_table)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    inputs.append(itab)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    if has_scale:
        isc = _pad_rows_to(item_scale.reshape(-1).astype(jnp.float32),
                           Ipad, fill=1.0).reshape(n_chunks, c)
        inputs.append(isc)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    scratch = [
        pltpu.VMEM((block_q, r), user_table.dtype),   # gathered rows
        pltpu.SMEM((1, block_q), jnp.int32),          # staged indices
        pltpu.VMEM((2, c, r), item_table.dtype),      # chunk dbl buffer
    ]
    if has_scale:
        scratch.append(pltpu.VMEM((2, 1, c), jnp.float32))
    scratch += [
        pltpu.SemaphoreType.DMA,                      # user rows
        pltpu.SemaphoreType.DMA,                      # index staging
        pltpu.SemaphoreType.DMA((2,)),                # item chunks
    ]
    if has_scale:
        scratch.append(pltpu.SemaphoreType.DMA((2,)))

    kernel = functools.partial(_fused_topk_kernel, n_chunks, c, k,
                               n_items, has_scale)
    scores, ids = pl.pallas_call(
        kernel,
        grid=(Bp // block_q,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_q, k), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*inputs)
    return scores[:B], ids[:B]


def fused_topk_reference(user_table: jax.Array, idx: jax.Array,
                         item_table: jax.Array,
                         user_scale: Optional[jax.Array] = None,
                         item_scale: Optional[jax.Array] = None,
                         base: Optional[jax.Array] = None, *, k: int,
                         n_items: int) -> Tuple[jax.Array, jax.Array]:
    """jnp mirror of the kernel (gather, dequantize, full [B, I] score
    matrix, top_k) — the oracle for the parity tests, never a
    fallback. Materializes the score matrix: this is the baseline the
    kernel exists to beat."""
    # ptpu: allow[materialized-gather] — [B, r] serving row fetch
    # bounded by the dispatch batch, mirroring _serve_topk
    vecs = user_table[idx].astype(jnp.float32)
    if user_scale is not None:
        # ptpu: allow[materialized-gather] — [B]-bounded scale fetch
        vecs = vecs * user_scale.reshape(-1)[idx][:, None]
    items = item_table.astype(jnp.float32)
    scores = vecs @ items.T
    if item_scale is not None:
        scores = scores * item_scale.reshape(1, -1)
    Ip = item_table.shape[0]
    gid = jnp.arange(Ip, dtype=jnp.int32)
    if base is not None:
        gid = gid + jnp.asarray(base).astype(jnp.int32).reshape(())
    scores = jnp.where((gid < n_items)[None, :], scores, -jnp.inf)
    s, pos = jax.lax.top_k(scores, min(k, Ip))
    ids = jnp.take(gid, pos)
    if k > Ip:  # mirror the kernel's fixed [B, k] shape
        pad = k - Ip
        s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad)))
    return s, ids


_probes = _probe.CompileProbes()


def fused_topk_refusal(batch: int, rank: int, n_rows: int, k: int,
                       wire_dtype="float32") -> Optional[str]:
    """Why the fused serving kernel will NOT run for a ``[batch]``
    dispatch of top-``k`` over an ``[n_rows, rank]`` item table of
    ``wire_dtype`` on the attached backend — None when it compiles at
    exactly those shapes. Keeps the compiler's message verbatim; the
    deploy-time bind raises it for an explicit ``serving_topk="fused"``
    and ``/status.json`` carries it for a kernel ``auto`` skipped.
    Without a TPU attached the answer is ``_probe.NO_TPU`` (interpret
    mode is for CPU tests only)."""
    dt = jnp.dtype(wire_dtype)
    batch, rank, n_rows, k = int(batch), int(rank), int(n_rows), int(k)
    utab = jax.ShapeDtypeStruct((max(batch, _BLOCK_Q), rank), dt)
    itab = jax.ShapeDtypeStruct((n_rows, rank), dt)
    idx = jax.ShapeDtypeStruct((batch,), jnp.int32)
    scale = (jax.ShapeDtypeStruct((utab.shape[0], 1), jnp.float32),
             jax.ShapeDtypeStruct((n_rows, 1), jnp.float32)) \
        if dt == jnp.int8 else (None, None)
    return _probes.refusal(
        (f"B{batch}", f"r{rank}", f"I{n_rows}", f"k{k}", dt.name),
        lambda: fused_topk.lower(utab, idx, itab, *scale, k=k,
                                 n_items=n_rows).compile())


#: every refusal probed so far,
#: ``B<batch>/r<rank>/I<rows>/k<k>/<dtype>`` → compiler message
refusals = _probes.refusals
reset_support_cache_for_tests = _probes.clear


def fused_topk_dispatch(user_table: jax.Array, idx: jax.Array,
                        item_table: jax.Array,
                        user_scale: Optional[jax.Array] = None,
                        item_scale: Optional[jax.Array] = None,
                        base: Optional[jax.Array] = None, *, k: int,
                        n_items: int) -> Tuple[jax.Array, jax.Array]:
    """Backend-aware fused entry (what ``models/als.py::_device_topk``
    calls when the serving top-k resolves to "fused"):

    - TPU → the compiled kernel, or the compiler's error if it refuses:
      a request for this kernel never runs anything else;
    - no TPU → interpret-mode kernel: an explicit topk="fused" on CPU
      is a debugging run and should exercise the REAL kernel (this is
      what tier-1 covers without a TPU).
    """
    return fused_topk(user_table, idx, item_table, user_scale,
                      item_scale, base, k=k, n_items=n_items,
                      interpret=not _probe.tpu_attached())
