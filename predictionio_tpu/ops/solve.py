"""Batched small linear solves for alternating least squares.

The per-row normal equations of ALS are rank×rank SPD systems — hundreds
of thousands of them per half-iteration (the role of the per-user LAPACK
calls MLlib's ALS makes inside each Spark task,
``ALSAlgorithm.scala:75-85``). XLA's batched Cholesky lowers each tiny
factorization to a serial column loop that leaves the chip almost idle
(measured: 1.15s for 138k×64×64 on a v5e — ~20 GFLOP/s). The Pallas
kernel here instead lays the batch out **along the 128 vector lanes**
(``[col, row, batch]``) so one program factors 128 matrices in lockstep:
every Cholesky column step is a full-width VPU op, and storing L by
columns makes both triangular sweeps column-access-only (the backward
substitution against L^T reads columns of L, not rows).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

#: batch lanes per Pallas program — the TPU vector lane width.
_LANES = 128


#: past this padded rank the [rp, rp, 128] block + a same-size scratch
#: exceed VMEM (measured chip OOM at rp=128: 2×8.4MB). Up to _RP_ALIAS
#: the matrix stays in HBM and the kernel DMAs it into ONE VMEM scratch
#: it factors in place; beyond it no 128-lane layout fits (the lane dim
#: cannot shrink below 128 — Mosaic rejects sub-lane minor blocks) and
#: ``solve_spd_batch`` routes to XLA.
_RP_SCRATCH = 88   # scratch variant: 2·rp²·128·4B ≤ ~8MB
_RP_ALIAS = 128    # one-buffer variant: rp²·128·4B ≤ ~8.4MB
_PANEL = 8         # column-panel width of the big-rank trailing update


def _chol_body(A, b_ref, x_ref, acc, lref=None):
    """Factor + solve 128 SPD systems in lockstep.

    A: writable [r, r, B] ref (column, row, batch-in-lanes) already
    holding the input; b_ref/x_ref: [r, B]. The factorization happens
    in place: after step k, leading index k is column k of L (zeros
    above the diagonal). Both substitution sweeps are formulated
    column-access-only (forward right-looking, backward left-looking),
    so L is never transposed.

    With ``lref`` (a [r, B] scratch), the trailing rank-1 update runs
    in COLUMN PANELS of ``_PANEL`` instead of one full-matrix
    expression: ``A[:] - l⊗l`` materializes two matrix-sized
    temporaries on the VMEM stack (2×8.4MB at r=128 — the measured
    chip OOM even after the input/scratch aliasing), while the
    panelized form's temporaries are ``_PANEL``·r·B floats.
    """
    r = A.shape[0]
    B = A.shape[2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (r, B), 0)

    def at_row(v, k):
        # extract row k of a [r, B] VALUE as [1, B] — Pallas TPU has no
        # value-level dynamic_slice, so use a masked lane reduction
        return jnp.sum(v * (rows == k), axis=0, keepdims=True)

    def factor_step(k, carry):
        colk = A[k]  # [r, B]
        piv = at_row(colk, k)  # [1, B]
        inv_sqrt = jax.lax.rsqrt(jnp.maximum(piv, 1e-30))
        l = colk * inv_sqrt * (rows >= k)
        if lref is None:
            A[:] = A[:] - l[:, None, :] * l[None, :, :]
        else:
            lref[:] = l

            def panel(ci, c):
                c0 = ci * _PANEL
                lp = lref[pl.ds(c0, _PANEL)]          # [P, B]
                A[pl.ds(c0, _PANEL)] = (
                    A[pl.ds(c0, _PANEL)]
                    - lp[:, None, :] * l[None, :, :])  # [P, r, B] temps
                return c

            jax.lax.fori_loop(0, r // _PANEL, panel, 0, unroll=False)
        A[k] = l
        return carry

    jax.lax.fori_loop(0, r, factor_step, 0, unroll=False)

    # forward substitution: L y = b  (acc morphs b → y)
    acc[:] = b_ref[:]

    def fwd_step(k, carry):
        Lk = A[k]  # [r, B] — column k of L
        lkk = at_row(Lk, k)
        yk = at_row(acc[:], k) / jnp.maximum(lkk, 1e-30)
        acc[:] = jnp.where(rows == k, yk,
                           acc[:] - Lk * yk * (rows > k))
        return carry

    jax.lax.fori_loop(0, r, fwd_step, 0, unroll=False)

    # backward substitution, left-looking: x_k = (y_k - Σ_{j>k} L[j,k]·x_j)
    # / L[k,k]. The sum runs over COLUMN k of L — exactly what the column
    # storage indexes. ``acc`` rows > k already hold x, rows ≤ k still y.
    def bwd_step(i, carry):
        k = r - 1 - i
        Lk = A[k]  # [r, B] — column k of L
        lkk = at_row(Lk, k)
        s = jnp.sum(Lk * acc[:] * (rows > k), axis=0, keepdims=True)
        xk = (at_row(acc[:], k) - s) / jnp.maximum(lkk, 1e-30)
        acc[:] = jnp.where(rows == k, xk, acc[:])
        return carry

    jax.lax.fori_loop(0, r, bwd_step, 0, unroll=False)
    # write batch-major [B, r]: emitting the transpose HERE (one small
    # VMEM shuffle per block) instead of returning [r, B] and lazily
    # transposing outside makes the pallas output physically row-major.
    # The lazy transpose was implemented by XLA as a layout flip
    # ({0,1}) that propagated through reshape into the training loop's
    # factor carry — and gathering 20M rows from a {0,1}-laid factor
    # table ran at ~40 GB/s vs ~260 GB/s row-major (the round-4 trace's
    # dominant cost, fusion.534).
    x_ref[:] = acc[:].T


def _chol_solve_kernel(a_ref, b_ref, x_ref, A, acc):
    """Scratch variant (rp <= _RP_SCRATCH): copy the input block into
    VMEM scratch and factor there."""
    A[:] = a_ref[:]
    _chol_body(A, b_ref, x_ref, acc)


def _chol_solve_kernel_inplace(a_hbm, b_ref, x_ref, A, acc, lref, sem):
    """One-buffer variant (rp <= _RP_ALIAS): the matrix block arrives
    as an HBM ref and is DMA'd into the single VMEM scratch ``A``, which
    the factorization then overwrites in place; the panelized update
    (``lref``) keeps kernel temporaries off the matrix scale — together
    these are what let rank 128 fit VMEM. (The earlier realization
    aliased a VMEM input block to an output block and factored "in
    place" there. Inside a whole-training program XLA stages the
    operand and the aliased result as TWO VMEM buffers — first seen on
    the v5e as a 16.18 MiB scoped-VMEM OOM, then, with the limit
    raised, as non-finite factors: the kernel was reading a result
    buffer that never held the input.)"""
    copy = pltpu.make_async_copy(a_hbm, A, sem)
    copy.start()
    copy.wait()
    _chol_body(A, b_ref, x_ref, acc, lref=lref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _solve_spd_pallas(A: jax.Array, b: jax.Array,
                      interpret: bool = False) -> jax.Array:
    """Pallas path: A [n, r, r] SPD (jitter already applied), b [n, r].
    Requires r <= _RP_ALIAS after sublane padding (the caller routes
    larger ranks to XLA)."""
    n, r = A.shape[0], A.shape[-1]
    rp = _padded_rank(r)
    assert rp <= _RP_ALIAS, f"rank {r} exceeds the Pallas VMEM budget"
    lanes = _LANES
    np_ = ((n + lanes - 1) // lanes) * lanes
    # pad rank with identity (keeps matrices SPD) and batch with identity
    if rp != r or np_ != n:
        eye = jnp.eye(rp, dtype=A.dtype)
        Ap = jnp.zeros((np_, rp, rp), A.dtype) + eye
        Ap = Ap.at[:n, :r, :r].set(A)
        bp = jnp.zeros((np_, rp), b.dtype).at[:n, :r].set(b)
    else:
        Ap, bp = A, b
    # batch-in-lanes layout: [col, row, batch] (A is symmetric, so the
    # (row, col) vs (col, row) choice is immaterial on input)
    At = jnp.transpose(Ap, (2, 1, 0))
    bt = jnp.transpose(bp, (1, 0))
    mat_spec = pl.BlockSpec((rp, rp, lanes), lambda i: (0, 0, i),
                            memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((rp, lanes), lambda i: (0, i),
                            memory_space=pltpu.VMEM)
    # solutions come out batch-major [np_, rp] (see _chol_body's final
    # write) so no downstream transpose/layout-flip reaches the caller
    xvec_spec = pl.BlockSpec((lanes, rp), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    if rp <= _RP_SCRATCH:
        # scratch variant: input block + same-size scratch fit VMEM
        xrows = pl.pallas_call(
            _chol_solve_kernel,
            grid=(np_ // lanes,),
            in_specs=[mat_spec, vec_spec],
            out_specs=xvec_spec,
            out_shape=jax.ShapeDtypeStruct((np_, rp), A.dtype),
            scratch_shapes=[
                pltpu.VMEM((rp, rp, lanes), jnp.float32),
                pltpu.VMEM((rp, lanes), jnp.float32),
            ],
            interpret=interpret,
        )(At, bt)
    else:
        # one-buffer variant for big ranks. Two VMEM measures, both
        # necessary at rp=128 (measured chip OOMs otherwise):
        # - the matrix stays in HBM (``pltpu.HBM``) and the kernel DMAs it
        #   into its one [rp, rp, 128] scratch — no VMEM input block,
        #   no matrix-sized output;
        # - each 128-lane slice is a GRIDLESS pallas_call driven by
        #   ``lax.map``, so nothing matrix-sized is double-buffered.
        nb = np_ // lanes
        Ab = jnp.moveaxis(At.reshape(rp, rp, nb, lanes), 2, 0)
        bb = jnp.moveaxis(bt.reshape(rp, nb, lanes), 1, 0)
        whole = pl.BlockSpec(memory_space=pltpu.VMEM)

        def one(args):
            a, b2 = args
            return pl.pallas_call(
                _chol_solve_kernel_inplace,
                in_specs=[pl.BlockSpec(memory_space=pltpu.HBM), whole],
                out_specs=whole,
                out_shape=jax.ShapeDtypeStruct((lanes, rp), A.dtype),
                scratch_shapes=[
                    pltpu.VMEM((rp, rp, lanes), jnp.float32),
                    pltpu.VMEM((rp, lanes), jnp.float32),
                    pltpu.VMEM((rp, lanes), jnp.float32),
                    pltpu.SemaphoreType.DMA(()),
                ],
                interpret=interpret,
            )(a, b2)

        xs = jax.lax.map(one, (Ab, bb))          # [nb, lanes, rp]
        xrows = xs.reshape(np_, rp)
    return xrows[:n, :r]


def _solver_mode() -> str:
    """"pallas" | "xla" | "auto" — "auto" defers the choice to LOWERING
    time via ``lax.platform_dependent``, so the decision tracks the
    platform the arrays actually compile for. An explicit "pallas"
    compiles the kernel or raises the compiler's message; nothing
    stands in for it."""
    mode = os.environ.get("PTPU_SPD_SOLVER", "auto")
    return mode if mode in ("pallas", "xla") else "auto"


def _padded_rank(r: int) -> int:
    return max(((r + 7) // 8) * 8, 8)


def solver_variant(rank: int, dtype=jnp.float32) -> str:
    """Which realization :func:`solve_spd_batch` runs for f32 systems of
    this rank on the attached backend: "pallas-scratch" (padded rank ≤
    ``_RP_SCRATCH``), "pallas-inplace" (≤ ``_RP_ALIAS``) or "xla" — the
    ``solver`` field of the train log line."""
    mode = _solver_mode()
    rp = _padded_rank(rank)
    if jnp.dtype(dtype) != jnp.float32 or mode == "xla" \
            or rp > _RP_ALIAS:
        return "xla"
    if mode == "auto" and jax.default_backend() != "tpu":
        return "xla"
    return "pallas-scratch" if rp <= _RP_SCRATCH else "pallas-inplace"


def _solve_spd_pallas_nd(A: jax.Array, b: jax.Array,
                         mesh: Optional[Mesh] = None,
                         interpret: bool = False) -> jax.Array:
    """:func:`_solve_spd_pallas` for arbitrary leading batch dims (like
    LAPACK's) and, under a ``mesh``, for sharded systems: GSPMD cannot
    partition a Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned" — how sharded training first failed on four real
    chips), so each device solves its own rows under ``shard_map``. The
    leading axis is sharded over every mesh axis when the devices
    divide it (the ``[d, B, r, r]`` row blocks of the trainer, the flat
    row-sharded accumulators of split mode); otherwise the systems are
    solved replicated (the few rows of an L-sharded skinny bucket)."""
    r = A.shape[-1]

    def local(A, b):
        x = _solve_spd_pallas(A.reshape(-1, r, r), b.reshape(-1, r),
                              interpret=interpret)
        return x.reshape(*A.shape[:-2], r)

    if mesh is None:
        return local(A, b)
    spec = P(tuple(mesh.axis_names)) \
        if A.shape[0] % mesh.devices.size == 0 else P()
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)(A, b)


def solve_spd_batch(A: jax.Array, b: jax.Array, jitter: float = 1e-6,
                    mesh: Optional[Mesh] = None) -> jax.Array:
    """Solve ``A[i] x = b[i]`` for a batch of SPD matrices.

    A: [n, r, r], b: [n, r] → x: [n, r]. A small diagonal jitter keeps
    Cholesky stable for rows with empty histories (A = λI only).

    On TPU this dispatches to the lane-batched Pallas Cholesky kernel;
    on CPU (tests) it uses XLA's ``cho_factor``/``cho_solve``. Override
    with ``PTPU_SPD_SOLVER={auto,pallas,xla}``. ``mesh`` is the mesh
    the systems are sharded over, if any (the Pallas kernel then runs
    per device, :func:`_solve_spd_pallas_nd`).
    """
    r = A.shape[-1]
    A = A + jitter * jnp.eye(r, dtype=A.dtype)

    def _pallas(A, b):
        return _solve_spd_pallas_nd(A, b, mesh)

    def _xla(A, b):
        chol, lower = jax.scipy.linalg.cho_factor(A)
        return jax.scipy.linalg.cho_solve((chol, lower),
                                          b[..., None])[..., 0]

    # the Pallas kernel's VMEM scratch is f32; non-f32 systems take the
    # XLA path rather than hitting a dtype-mismatched kernel. Ranks past
    # the VMEM budget (_RP_ALIAS) have no 128-lane Pallas layout at all.
    mode = _solver_mode()
    rp = _padded_rank(r)
    if A.dtype != jnp.float32 or mode == "xla" or rp > _RP_ALIAS:
        return _xla(A, b)
    if mode == "pallas":
        return _pallas(A, b)
    # "auto": pick per LOWERING platform (Mosaic lowers on TPU only).
    # A cpu-default process can never lower the Pallas branch anywhere,
    # and this jax's platform_dependent still tries to when the call
    # sits inside a fori_loop (the fused trainer) — short-circuit.
    if jax.default_backend() == "cpu":
        return _xla(A, b)
    return jax.lax.platform_dependent(A, b, tpu=_pallas, default=_xla)


def gramian(factors: jax.Array) -> jax.Array:
    """``F^T F`` in float32 — the rank×rank Gramian shared by every row's
    normal equations (computed once per half-iteration; under a sharded
    ``factors`` XLA lowers the contraction to partial products + an
    all-reduce over the mesh)."""
    f32 = factors.astype(jnp.float32)
    return f32.T @ f32
