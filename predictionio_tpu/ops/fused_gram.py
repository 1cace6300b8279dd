"""Fused gather+Gramian Pallas kernel — the HBM-roofline attack.

BENCH_r05 showed ALS training bandwidth-bound, not compute-bound: 75%
HBM utilization at 0.6% MFU (1.6% at rank 128). The reason is the shape
of the inner loop: the XLA half-step materializes the gathered factor
tensor ``F = fixed[indices]`` as a ``[B, L, r]`` HBM temp (written once,
read back at least once) before the weighted-Gramian einsum ever runs —
≥3 HBM touches per gathered element for ~2r flops each. This is exactly
the embedding-gather access pattern Tensor Casting (arXiv 2010.13100)
co-designs TPU kernels for.

This kernel fuses the gather INTO the Gramian accumulation:

- per history chunk, the chunk's indices hop from their VMEM block into
  a small SMEM tile, whose scalar reads drive per-row DMAs that pull
  fixed-factor rows from HBM directly into double-buffered ``[chunk,r]``
  VMEM tiles — the next chunk's DMAs in flight while the MXU contracts
  the current one (bf16 on the wire when the caller passes the
  ``ALSParams.gather_dtype`` shadow);
- ``Σ_l wa·f fᵀ`` accumulates in an f32 VMEM scratch tile; the fused
  RHS ``Σ_l wb·f`` rides the same resident chunk, so the SPD solve
  consumes kernel outputs directly;
- the ``[B, L, r]`` gather temp never exists in HBM.

Per gathered entry (~2r+2r flops of Gramian+RHS work) the HBM traffic
drops from ``~3·r·4`` B (write + read-back of the temp, plus the table
read) to ``r·wire_bytes + 12`` B (the row DMA plus index and weights) —
arithmetic intensity rises ~3x on the f32 wire and ~6x on the bf16
wire, enough to lift the op off the HBM roof (the roofline probe's
``arithmetic_intensity`` field measures the achieved number).

Entry points:

- :func:`fused_gram` — the kernel itself (``interpret=True`` runs it
  on any backend for tests/debugging);
- :func:`fused_gram_dispatch` — backend-aware: the compiled kernel on
  TPU (a kernel the compiler refuses RAISES with the compiler's
  message — nothing stands in for it), the interpret-mode kernel
  elsewhere (explicit ``gram_mode="fused"`` on a CPU is a debugging
  run);
- :func:`fused_gram_reference` — the jnp mirror the accuracy tests
  hold the kernel against (never a fallback);
- :func:`fused_gram_refusal` — compile probe at the shapes about to
  run; returns the compiler's message when it refuses.

Wired as ``ALSParams(gram_mode="fused")`` through
``models/als.py::_lhs_fn`` (which owns the only gather) and picked by
``gram_mode="auto"`` via :mod:`.gram_autotune`. See docs/kernels.md for
the VMEM budget math and the overlapped-all-reduce mesh schedule.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _probe

#: rows of A/b produced per grid step. Small on purpose: each row's
#: history chunks pipeline through the double buffer, so the block size
#: only bounds the weight blocks and the output tile.
_BLOCK_ROWS = 8

#: history slots DMA'd per double-buffer fill. Bounds the VMEM working
#: set at ``2·chunk·r·wire_bytes`` (512 KiB at r=128 f32, half that on
#: the bf16 wire) and the SMEM index tile at ``2·chunk·4`` = 4 KiB,
#: however long the padded history L grows — bucketed layouts reach
#: L=8192, which would fit neither VMEM nor SMEM un-chunked.
_L_CHUNK = 512


def fused_vmem_bytes(L: int, rank: int, wire_bytes: int = 4,
                     block_rows: int = _BLOCK_ROWS,
                     chunk: int = _L_CHUNK) -> int:
    """VMEM bytes the kernel holds live per core (docs/kernels.md):
    double-buffered factor tiles, the three weight/index blocks, the
    f32 accumulators and the output tile."""
    chunk = min(chunk, L)
    fbuf = 2 * chunk * rank * wire_bytes
    blocks = 3 * block_rows * L * 4           # idx + wa + wb blocks
    acc = rank * rank * 4 + rank * 4          # f32 accumulators
    out = block_rows * (rank * rank + rank) * 4
    return fbuf + blocks + acc + out


def _fused_gram_kernel(n_chunks: int, chunk: int,
                       idx_ref, wa_ref, wb_ref, tab_ref,
                       A_ref, b_ref, fbuf, ibuf, acc, bacc,
                       sems, isems):
    """One ``[BR, L]`` block: for each row, stream its history through
    the double-buffered ``[chunk, r]`` VMEM tile (per-slot HBM row DMAs
    for step s+1 issued before step s's contraction waits) and
    accumulate ``Σ wa·f fᵀ`` / ``Σ wb·f`` in f32 VMEM. The flat step
    sequence walks (row, chunk) pairs so the pipeline never drains
    between rows."""
    BR, Lp = idx_ref.shape

    def fetch(s, slot):
        row = s // n_chunks
        base = (s % n_chunks) * chunk
        # the chunk's indices hop VMEM→SMEM first: row DMAs need
        # scalar source addresses, and a [BR, L] SMEM *block* would
        # blow the scalar-memory budget at bucketed L
        icopy = pltpu.make_async_copy(
            idx_ref.at[pl.ds(row, 1), pl.ds(base, chunk)],
            ibuf.at[pl.ds(slot, 1), :],
            isems.at[slot])
        icopy.start()
        icopy.wait()

        def issue(l, c):
            pltpu.make_async_copy(
                tab_ref.at[pl.ds(ibuf[slot, l], 1), :],
                fbuf.at[slot, pl.ds(l, 1), :],
                sems.at[slot]).start()
            return c

        jax.lax.fori_loop(0, chunk, issue, 0, unroll=False)

    def drain(slot):
        # the wait descriptor only carries the copy SIZE (one [1, r]
        # row); a fixed source slice stands in for all of them
        def wait(l, c):
            pltpu.make_async_copy(
                tab_ref.at[pl.ds(0, 1), :],
                fbuf.at[slot, pl.ds(l, 1), :],
                sems.at[slot]).wait()
            return c

        jax.lax.fori_loop(0, chunk, wait, 0, unroll=False)

    n_steps = BR * n_chunks
    fetch(0, 0)

    def step(s, carry):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < n_steps)
        def _():
            fetch(s + 1, jax.lax.rem(s + 1, 2))

        drain(slot)
        row = s // n_chunks
        ch = s % n_chunks
        # upcast AFTER the wire: bf16 rows contract with f32
        # accumulation (preferred_element_type), the TPU-native
        # mixed-precision idiom — the HBM bytes were the bf16 rows
        F = fbuf[slot].astype(jnp.float32)               # [chunk, r]
        wa = wa_ref[pl.ds(row, 1), pl.ds(ch * chunk, chunk)]
        wb = wb_ref[pl.ds(row, 1), pl.ds(ch * chunk, chunk)]
        G = jax.lax.dot_general(
            F * wa.reshape(chunk, 1), F, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [r, r]
        bb = jax.lax.dot_general(
            wb, F, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [1, r]

        @pl.when(ch == 0)
        def _():
            acc[:] = G
            bacc[:] = bb

        @pl.when(ch > 0)
        def _():
            acc[:] = acc[:] + G
            bacc[:] = bacc[:] + bb

        @pl.when(ch == n_chunks - 1)
        def _():
            A_ref[pl.ds(row, 1)] = acc[:][None]
            b_ref[pl.ds(row, 1)] = bacc[:]

        return carry

    jax.lax.fori_loop(0, n_steps, step, 0, unroll=False)


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    n = x.shape[axis]
    if n == to:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, to - n)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("block_rows", "chunk",
                                             "interpret"))
def fused_gram(table: jax.Array, idx: jax.Array, wa: jax.Array,
               wb: jax.Array, *, block_rows: int = _BLOCK_ROWS,
               chunk: Optional[int] = None,
               interpret: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """Fused gather + weighted Gramian from an HBM-resident ``table``
    [m, r] (f32, or the bf16 shadow for a bf16 wire): returns
    ``(A [B, r, r] f32, b [B, r] f32)`` with ``A[i] = Σ_l wa[i,l]·f fᵀ``
    and ``b[i] = Σ_l wb[i,l]·f`` over ``f = table[idx[i, l]]``.

    Padding slots must carry w=0 (idx may point at any valid row);
    B and L are padded to block multiples internally and sliced back —
    ragged tails are the caller's normal case, not an error."""
    B, L = idx.shape
    m, r = table.shape
    Lc = min(chunk or _L_CHUNK, L)
    Lp = -(-L // Lc) * Lc
    Bp = max(-(-B // block_rows) * block_rows, block_rows)
    idx = _pad_axis(_pad_axis(idx.astype(jnp.int32), 1, Lp), 0, Bp)
    wa = _pad_axis(_pad_axis(wa.astype(jnp.float32), 1, Lp), 0, Bp)
    wb = _pad_axis(_pad_axis(wb.astype(jnp.float32), 1, Lp), 0, Bp)
    # `ptpu check` (vmem-overbudget) proves this bound statically over
    # the autotune rank grid; assert it at trace time too, so an
    # exotic (L, rank, chunk) combination from a caller-supplied
    # override fails loudly on the host instead of OOMing VMEM
    # mid-train (shapes are static under jit — this costs nothing)
    assert fused_vmem_bytes(Lp, r, table.dtype.itemsize, block_rows,
                            Lc) < 16 * 1024 * 1024, \
        f"fused_gram VMEM working set exceeds the ~16 MiB/core " \
        f"budget at rank {r}, chunk {Lc}, L {Lp} (docs/kernels.md)"
    n_chunks = Lp // Lc
    kernel = functools.partial(_fused_gram_kernel, n_chunks, Lc)
    A, b = pl.pallas_call(
        kernel,
        grid=(Bp // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, Lp), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, Lp), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, Lp), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            # the factor table STAYS in HBM — rows are DMA'd on demand;
            # this is the whole point (a VMEM-resident BlockSpec would
            # cap m·r at the ~16MB core budget)
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, r, r), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, r), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, r, r), jnp.float32),
            jax.ShapeDtypeStruct((Bp, r), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, Lc, r), table.dtype),   # row double buffer
            pltpu.SMEM((2, Lc), jnp.int32),        # staged index chunk
            pltpu.VMEM((r, r), jnp.float32),       # Gramian accumulator
            pltpu.VMEM((1, r), jnp.float32),       # RHS accumulator
            pltpu.SemaphoreType.DMA((2,)),         # row DMAs
            pltpu.SemaphoreType.DMA((2,)),         # index staging
        ],
        interpret=interpret,
    )(idx, wa, wb, table)
    return A[:B], b[:B]


def fused_gram_reference(table: jax.Array, idx: jax.Array,
                         wa: jax.Array, wb: jax.Array
                         ) -> Tuple[jax.Array, jax.Array]:
    """jnp mirror of the kernel (gather, upcast, f32 contraction) —
    the oracle for the accuracy tests, never a fallback. Materializes
    the gather temp: this is the baseline the kernel exists to beat."""
    F = table[idx].astype(jnp.float32)  # [B, L, r]
    A = jnp.einsum("blr,bls,bl->brs", F, F, wa.astype(jnp.float32))
    b = jnp.einsum("blr,bl->br", F, wb.astype(jnp.float32))
    return A, b


_probes = _probe.CompileProbes()


def fused_gram_refusal(rank: int, wire_dtype="float32",
                       hist_len: int = _L_CHUNK) -> Optional[str]:
    """Why the fused kernel will NOT run at ``(rank, wire_dtype,
    hist_len)`` on the attached backend — None when it compiles.

    The probe compiles the kernel with the block, chunk and scratch
    shapes the real call at that history length gets (one
    ``[_BLOCK_ROWS, hist_len]`` grid step; the table's row count and
    the batch only set the grid extent, not what Mosaic compiles) and
    keeps the compiler's message verbatim. ``gram_mode="auto"``
    consumers report that message and train on einsum; an explicit
    ``gram_mode="fused"`` never consults this — it compiles the kernel
    or raises. Without a TPU attached the answer is
    ``_probe.NO_TPU`` (interpret mode is for CPU tests only)."""
    dt = jnp.dtype(wire_dtype)
    tab = jax.ShapeDtypeStruct((_BLOCK_ROWS, int(rank)), dt)
    idx = jax.ShapeDtypeStruct((_BLOCK_ROWS, int(hist_len)), jnp.int32)
    w = jax.ShapeDtypeStruct((_BLOCK_ROWS, int(hist_len)), jnp.float32)
    return _probes.refusal(
        (f"r{int(rank)}", dt.name, f"L{int(hist_len)}"),
        lambda: fused_gram.lower(tab, idx, w, w).compile())


#: every refusal probed so far, ``r<rank>/<dtype>/L<hist_len>`` →
#: compiler message
refusals = _probes.refusals
reset_support_cache_for_tests = _probes.clear


def fused_gram_dispatch(table: jax.Array, idx: jax.Array, wa: jax.Array,
                        wb: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Backend-aware fused entry (the ``gram_mode="fused"`` realization
    ``models/als.py::_lhs_fn`` calls):

    - TPU → the compiled kernel, or the compiler's error if it refuses
      the shapes: a request for this kernel never runs anything else. A
      CPU lowering of the same trace (virtual-mesh dryruns) runs it
      interpreted, so the numbers match the device run;
    - no TPU → interpret-mode kernel: an explicit ``gram_mode="fused"``
      on CPU is a debugging run and should exercise the REAL kernel
      (this is what tier-1 covers without a TPU).
    """
    if _probe.tpu_attached():
        return jax.lax.platform_dependent(
            table, idx, wa, wb,
            tpu=lambda t, i, a, b: fused_gram(t, i, a, b),
            default=lambda t, i, a, b: fused_gram(t, i, a, b,
                                                  interpret=True))
    return fused_gram(table, idx, wa, wb, interpret=True)
