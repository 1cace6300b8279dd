"""Weighted-gram variants for the ALS normal equations.

The per-row system build Σ_l w·f fᵀ is where the FLOPs are
(``ALSAlgorithm.scala:75-85`` role). At rank 64 the straightforward
batched einsum ``[B,L,64]→[B,64,64]`` runs M=N=64 matmuls on a 128×128
MXU — a quarter of the array (measured ~3-5 TF/s f32 on a v5e whose
bf16 peak is 197, BASELINE.md).

``gram_pairs`` packs TWO rank-64 systems per MXU tile: rows are paired
along the feature axis, one ``[B/2, L, 128]²`` einsum produces
``[B/2, 128, 128]`` tiles whose two diagonal 64×64 blocks are the two
rows' grams. The multiply count doubles (the off-diagonal blocks are
discarded) but every multiply now runs on a FULL MXU tile — a net win
exactly when the op is MXU-bound, which ``benchmarks/gram_profile.py``
measures per shape. Opt-in via ``ALSParams(gram_mode="pair")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def gram_weighted(F: jax.Array, w: jax.Array,
                  bf16: bool = False) -> jax.Array:
    """Baseline batched weighted gram: ``A[..., i, :, :] = Σ_l w·f fᵀ``.
    F: [..., L, r], w: [..., L] → [..., r, r]."""
    if bf16:
        Fw = (F * w[..., None]).astype(jnp.bfloat16)
        Fc = F.astype(jnp.bfloat16)
        return jnp.einsum("...lr,...ls->...rs", Fw, Fc,
                          preferred_element_type=jnp.float32)
    # F may still be a bf16 gather shadow even when the bf16 *compute*
    # mode is off — pin the accumulator wide either way
    return jnp.einsum("...lr,...ls,...l->...rs", F, F, w,
                      preferred_element_type=jnp.float32)


def gram_pairs(F: jax.Array, w: jax.Array,
               bf16: bool = False) -> jax.Array:
    """Pair-packed weighted gram (see module docstring): same result as
    :func:`gram_weighted` with rows packed two-per-MXU-tile. Requires an
    EVEN number of rows on the second-to-last batch axis (callers fall
    back to :func:`gram_weighted` otherwise)."""
    *lead, n, L, r = F.shape
    assert n % 2 == 0, "gram_pairs needs an even row count"
    F0, F1 = F[..., 0::2, :, :], F[..., 1::2, :, :]
    Fp = jnp.concatenate([F0, F1], axis=-1)  # [..., n/2, L, 2r]
    Wp = jnp.concatenate([F0 * w[..., 0::2, :, None],
                          F1 * w[..., 1::2, :, None]], axis=-1)
    if bf16:
        Fp = Fp.astype(jnp.bfloat16)
        Wp = Wp.astype(jnp.bfloat16)
    G2 = jnp.einsum("...lr,...ls->...rs", Wp, Fp,
                    preferred_element_type=jnp.float32)
    # [..., n/2, 2r, 2r] → the two diagonal blocks, interleaved back
    A0 = G2[..., :r, :r]
    A1 = G2[..., r:, r:]
    return jnp.stack([A0, A1], axis=-3).reshape(*lead, n, r, r)


def _pair_padded(F: jax.Array, w: jax.Array, bf16: bool) -> jax.Array:
    """:func:`gram_pairs` for ANY row count: an odd batch is padded
    with one zero row (its gram is exactly zero) and sliced back. This
    is the ONE place odd-row handling lives — callers never assert
    evenness themselves (callers used to silently fall back to the
    einsum path on odd B, so the measured pair win evaporated on any
    odd tail block)."""
    n = F.shape[-3]
    if n % 2 == 0:
        return gram_pairs(F, w, bf16=bf16)
    padF = [(0, 0)] * F.ndim
    padF[-3] = (0, 1)
    padw = [(0, 0)] * w.ndim
    padw[-2] = (0, 1)
    out = gram_pairs(jnp.pad(F, padF), jnp.pad(w, padw), bf16=bf16)
    return out[..., :n, :, :]


def gram_dispatch(F: jax.Array, w: jax.Array, mode: str,
                  bf16: bool = False) -> jax.Array:
    """``mode``: "einsum" (baseline), "pair", "fused", or "auto".

    "auto" resolves through the persistent shape-keyed table
    (:mod:`.gram_autotune`): measured winners recorded by the bench's
    gram race / ``gram_profile.py --record``, then packaged defaults,
    then an MXU-tile-occupancy heuristic. The resolution happens at
    trace time (mode and shapes are static), so the choice costs
    nothing at run time.

    "fused" here means the caller materialized the gather before
    dispatching — with ``F`` already in hand there is nothing left to
    fuse, so it degrades to the baseline einsum. The fused entry point
    is ``models/als.py::_lhs_fn`` (table + indices, via
    :mod:`.fused_gram`), which intercepts the mode BEFORE the gather
    exists; landing here is the documented fallback for layouts the
    kernel doesn't cover (L-axis-sharded skinny buckets).

    Odd row counts are handled HERE (pad-and-slice, :func:`_pair_padded`)
    — "pair" applies to any B."""
    if mode == "auto":
        from .gram_autotune import best_mode

        mode = best_mode(F.shape[-1], bf16=bf16)
        if mode == "pair":
            # the autotuned winner describes the ACCELERATOR; on a CPU
            # lowering of the same trace (virtual-mesh dryruns on hosts
            # where the TPU plugin is the default backend) pair's 2x
            # multiplies are a pure loss — pick per lowering platform,
            # mirroring solve.py's platform gate
            return jax.lax.platform_dependent(
                F, w,
                tpu=lambda F, w: _pair_padded(F, w, bf16=bf16),
                default=lambda F, w: gram_weighted(F, w, bf16=bf16))
        return gram_weighted(F, w, bf16=bf16)
    if mode == "pair":
        return _pair_padded(F, w, bf16=bf16)
    return gram_weighted(F, w, bf16=bf16)


# -- VMEM-table fused gather+gram (Pallas) ----------------------------------
#
# The XLA half-step materializes F = table[idx] ([B, L, r] f32) in HBM
# and reads it back for the gram — ≥3 HBM touches per gathered element.
# When the FIXED factor table fits VMEM (27k items × rank 64 × 4B =
# 6.9MB on a ~16MB/core budget), this kernel streams only idx+weights
# (8B/entry) from HBM, gathers from the resident table, and runs the
# pair-packed MXU contraction entirely on-chip. Arithmetic intensity per
# entry goes from ~11 to ~1000 flops/byte — the HBM bound disappears.
#
# Mosaic's dynamic (vector-index) gather support is version-dependent;
# ``gram_table_supported()`` probes lowering once so callers can fall
# back to the XLA paths.

#: rows of A/b produced per kernel invocation step (must be even: the
#: MXU contraction packs two rows per 128-wide tile)
_BLOCK_ROWS = 16


def _gram_table_kernel(tab_ref, idx_ref, wa_ref, wb_ref, A_ref, b_ref):
    """One [Bt, L] block: per row pair, gather the pair's history rows
    from the VMEM-resident table, weight, and contract as ONE
    [L, 2r]ᵀ[L, 2r] MXU matmul whose diagonal r×r blocks are the two
    rows' grams (plus a [2, L]×[L, 2r] matmul for the b vectors)."""
    Bt, L = idx_ref.shape
    r = tab_ref.shape[1]
    tab = tab_ref[:]

    def step(p, carry):
        i0 = 2 * p
        idx2 = idx_ref[pl.ds(i0, 2), :]                        # [2, L]
        wa2 = wa_ref[pl.ds(i0, 2), :]
        wb2 = wb_ref[pl.ds(i0, 2), :]
        F2 = tab[idx2.reshape(2 * L)]                          # [2L, r]
        F0, F1 = F2[:L], F2[L:]
        Fp = jnp.concatenate([F0, F1], axis=1)                 # [L, 2r]
        Wp = jnp.concatenate([F0 * wa2[0][:, None],
                              F1 * wa2[1][:, None]], axis=1)
        G2 = jax.lax.dot_general(
            Wp, Fp, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [2r, 2r]
        B2 = jax.lax.dot_general(
            wb2, Fp, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [2, 2r]
        A_ref[pl.ds(i0, 1), :, :] = G2[None, :r, :r]
        A_ref[pl.ds(i0 + 1, 1), :, :] = G2[None, r:, r:]
        b_ref[pl.ds(i0, 1), :] = B2[None, 0, :r]
        b_ref[pl.ds(i0 + 1, 1), :] = B2[None, 1, r:]
        return carry

    jax.lax.fori_loop(0, Bt // 2, step, 0, unroll=False)


def gram_table_pallas(table: jax.Array, idx: jax.Array, wa: jax.Array,
                      wb: jax.Array, interpret: bool = False):
    """Fused gather+gram from a VMEM-resident ``table`` [m, r]:
    returns (A [B, r, r], b [B, r]) with
    ``A[i] = Σ_l wa[i,l]·f fᵀ`` and ``b[i] = Σ_l wb[i,l]·f`` over
    ``f = table[idx[i,l]]``. Pad slots carry w=0 (idx may point
    anywhere valid). B is padded to the block size internally."""
    B, L = idx.shape
    m, r = table.shape
    Bp = -(-B // _BLOCK_ROWS) * _BLOCK_ROWS
    if Bp != B:
        pad = ((0, Bp - B), (0, 0))
        idx = jnp.pad(idx, pad)
        wa = jnp.pad(wa, pad)
        wb = jnp.pad(wb, pad)
    A, b = pl.pallas_call(
        _gram_table_kernel,
        grid=(Bp // _BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((m, r), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BLOCK_ROWS, L), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BLOCK_ROWS, L), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BLOCK_ROWS, L), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS, r, r), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BLOCK_ROWS, r), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, r, r), jnp.float32),
            jax.ShapeDtypeStruct((Bp, r), jnp.float32),
        ],
        interpret=interpret,
    )(table, idx, wa, wb)
    return A[:B], b[:B]


_table_support: dict = {}


def gram_table_supported() -> bool:
    """Probe once whether the fused table kernel LOWERS on the attached
    backend (Mosaic's vector-gather support is version-dependent)."""
    try:
        dev = jax.devices()[0]
        if not (dev.platform == "tpu"
                or dev.device_kind.startswith("TPU")):
            return False
    except Exception:  # pragma: no cover
        return False
    cached = _table_support.get("tpu")
    if cached is not None:
        return cached
    try:
        tab = jnp.zeros((128, 64), jnp.float32)
        idx = jnp.zeros((_BLOCK_ROWS, 128), jnp.int32)
        w = jnp.zeros((_BLOCK_ROWS, 128), jnp.float32)
        jax.jit(gram_table_pallas).lower(tab, idx, w, w).compile()
        ok = True
    except Exception:  # noqa: BLE001 — lowering not supported
        ok = False
    _table_support["tpu"] = ok
    return ok
