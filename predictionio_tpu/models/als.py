"""Alternating least squares on a TPU mesh — explicit and implicit.

The north-star algorithm (SURVEY §7 hard part 1): the role MLlib ALS plays
for the reference's recommendation templates
(``tests/pio_tests/engines/recommendation-engine/src/main/scala/
ALSAlgorithm.scala:51-93`` explicit, ``examples/scala-parallel-
similarproduct/.../ALSAlgorithm.scala`` trainImplicit), re-designed
ALX-style (arXiv 2112.02194) instead of translating MLlib's block
partitioning + shuffle joins:

- Both factor matrices live **row-sharded across all mesh devices**; the
  per-row normal equations are built from padded per-row histories
  (static shapes, no ragged data on device) and solved as one batched
  Cholesky on the MXU.
- The rank×rank Gramian and the cross-shard factor gathers lower to XLA
  collectives (all-reduce / all-gather) over ICI — no hand-written
  NCCL/shuffle analogue.
- MLlib semantic parity: ALS-WR regularization (λ scaled by each row's
  rating count) and Hu-Koren-Volinsky implicit confidence
  c = 1 + alpha·r with the fixed-side Gramian as the preference-0
  baseline term.

One API covers the reference's L/P split: mesh=None (or 1 device) is the
local path, mesh of N shards the same code.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import numerics as _numerics
from ..ops.gram import gram_weighted
from ..ops.ragged import BucketedHistories, PaddedHistories, SplitHistories
from ..ops.solve import gramian, solve_spd_batch
from ..parallel.mesh import rows_spec
from ..utils.platform import enable_compilation_cache

@dataclass(frozen=True)
class ALSParams:
    """Hyperparameters, name-compatible with the reference template's
    engine.json (rank, numIterations, lambda, seed — ``tests/pio_tests/
    engines/recommendation-engine/engine.json``) plus the implicit-ALS
    knobs of the similar-product template."""

    rank: int = 10
    num_iterations: int = 10
    #: regularization — "lambda" in the reference's engine.json; the wire
    #: alias keeps those variant files working verbatim
    reg: float = field(default=0.01,
                       metadata={"aliases": ("lambda", "lambda_")})
    alpha: float = 1.0         # implicit confidence scale
    implicit_prefs: bool = False
    seed: int = 3
    max_history: Optional[int] = None  # cap padded history length
    scale_reg_by_count: bool = True    # ALS-WR λ·n_u scaling (MLlib parity)
    block_rows: Optional[int] = None   # per-device rows per update block
    #: "bfloat16" runs the normal-equation einsums on the MXU in bf16
    #: with f32 accumulation (the TPU-native mixed-precision idiom);
    #: factors and solves stay f32.
    matmul_dtype: str = "float32"
    #: "bfloat16" gathers each half-iteration's factor rows from a
    #: bf16 SHADOW of the (still-f32) factor table; master weights,
    #: gram accumulation and solves stay f32. Measured round 4 on a
    #: v5e: the f32 table (138k×64 = 35MB) is too big for XLA to keep
    #: VMEM-resident alongside the Pallas solve's scratch, so the 20M
    #: row gathers ran from HBM at ~6× the VMEM-resident cost — the
    #: whole-iteration bound. The 17.6MB shadow stays VMEM-staged:
    #: 1.98× per-iteration speedup for an ~0.4% relative perturbation
    #: of the normal-equation INPUTS (quality-checked by
    #: tests/test_als.py::TestGatherDtype).
    gather_dtype: str = "float32"
    #: History layout. "pad": one [n_rows, L] padded matrix per side
    #: (entries beyond L are DROPPED — round-1 semantics). "bucket":
    #: power-of-two length buckets, drop-free at ≤2× padding with MXU-deep
    #: contractions — the default drop-free layout. "split": rows longer
    #: than L become virtual rows scatter-added back (drop-free but the
    #: duplicate-index scatter serializes on TPU; kept for comparison).
    #: "auto": pad when nothing would be dropped (or when max_history
    #: explicitly caps), bucket otherwise.
    history_mode: str = "auto"

    def __post_init__(self):
        if self.matmul_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"matmul_dtype must be 'float32' or 'bfloat16', got "
                f"{self.matmul_dtype!r}")
        if self.gather_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"gather_dtype must be 'float32' or 'bfloat16', got "
                f"{self.gather_dtype!r}")
        if self.history_mode not in ("auto", "pad", "split", "bucket"):
            raise ValueError(
                f"history_mode must be 'auto', 'pad', 'split' or "
                f"'bucket', got {self.history_mode!r}")


@jax.tree_util.register_dataclass
@dataclass
class ALSModel:
    """Factor matrices (possibly padded past n_users/n_items for even
    sharding) + the id indexation back to entity-id strings.

    Registered as a pytree (factors are children; ids/params are static
    metadata) so persistence's ``jax.tree.map(to_host)`` reaches the
    device arrays inside."""

    user_factors: jax.Array = field(metadata=dict(static=False))
    item_factors: jax.Array = field(metadata=dict(static=False))
    n_users: int = field(metadata=dict(static=True))
    n_items: int = field(metadata=dict(static=True))
    user_ids: Optional[object] = field(default=None,
                                       metadata=dict(static=True))
    item_ids: Optional[object] = field(default=None,
                                       metadata=dict(static=True))
    params: ALSParams = field(default_factory=ALSParams,
                              metadata=dict(static=True))
    #: serving mesh when the factor tables are row-sharded
    #: (:func:`shard_model`); None for host/single-device models. Set
    #: at DEPLOY time only — persisted models never carry a mesh (a
    #: Mesh binds to live devices and must not enter the blob store).
    mesh: Optional[Mesh] = field(default=None, metadata=dict(static=True))


@dataclass(frozen=True)
class RatingsCOO:
    """Integer-indexed rating triples (host side)."""

    users: np.ndarray   # int32 [nnz]
    items: np.ndarray   # int32 [nnz]
    ratings: np.ndarray  # float32 [nnz]
    n_users: int
    n_items: int


def _lhs_fn(table: jax.Array, indices: jax.Array, wa: jax.Array,
            wb: jax.Array, *, bf16: bool):
    """Per-row normal-equation build — the ONE place the factor gather
    exists: ``A = Σ_l wa·f fᵀ`` and the fused RHS ``b = Σ_l wb·f`` over
    ``f = table[indices]``. ``table`` is the f32 factors or the bf16
    shadow (:func:`_shadow_lhs_fn` casts for callers that have not);
    weights arrive pre-masked so padding slots contribute exactly zero.

    Under a mesh GSPMD places both einsums: row-sharded blocks stay
    local, and the contraction over L of an L-axis-sharded skinny
    bucket becomes per-device partial Gramians + an all-reduce."""
    # gather_dtype="bfloat16": F stays bf16 INTO the einsums — the
    # upcast to f32 happens inside each dot's fusion (exact: the values
    # are already bf16-quantized) instead of as a standalone convert
    # materializing a second full-size F (measured 5.2ms per block in
    # the round-4 trace). Accumulation/solve stay f32 via promotion.
    # ptpu: allow[materialized-gather] — bounded by _auto_block_rows'
    # ~1GB block budget
    F = table[indices]  # [d, B, L, r] — cross-shard gather under a mesh
    A = gram_weighted(F, wa, bf16=bf16)
    # F can be the bf16 shadow: keep the RHS accumulation f32, matching
    # the Gramian side (ops/gram.py contract) — without this the Σ_l
    # wb·f sum runs at bf16 and fold-in solves drift
    b = jnp.einsum("...lr,...l->...r", F, wb,
                   preferred_element_type=jnp.float32)
    return A, b


def _shadow_lhs_fn(table_f32: jax.Array, indices: jax.Array,
                   wa: jax.Array, wb: jax.Array, *, bf16: bool):
    """:func:`_lhs_fn` over the bf16 SHADOW of an f32 table (the
    ``ALSParams.gather_dtype="bfloat16"`` wire): rows travel HBM→MXU
    as bf16, accumulation stays f32. The
    half-iteration impls pre-cast ONCE per half-step so every block
    shares one shadow buffer; this entry is for callers without that
    amortization (tests, one-shot solves)."""
    return _lhs_fn(table_f32.astype(jnp.bfloat16), indices, wa, wb,
                   bf16=bf16)


@functools.partial(jax.jit, static_argnames=("implicit", "scale_reg",
                                             "bf16", "mesh"))
def _update_block(fixed: jax.Array, G, indices: jax.Array,
                  values: jax.Array, counts: jax.Array, reg: float,
                  alpha: float, implicit: bool, scale_reg: bool,
                  bf16: bool = False,
                  mesh: Optional[Mesh] = None) -> jax.Array:
    """Recompute one block of rows, holding ``fixed`` constant.

    fixed: [m, r] (flat, row-sharded); G: [r, r] Gramian of ``fixed`` (only
    for implicit); indices/values: [d, B, L]; counts: [d, B] with leading
    axis sharded across all devices → new factors [d, B, r], same sharding.
    Padding entries carry value 0 and index 0; masks keep them inert.
    ``mesh`` (static) is the SPD solve's (``ops/solve.py``); GSPMD
    places the normal-equation einsums.
    """
    r = fixed.shape[-1]
    L = indices.shape[-1]
    valid = (jnp.arange(L)[None, None, :]
             < counts[:, :, None]).astype(jnp.float32)
    if implicit:
        # Hu-Koren-Volinsky: c = 1 + alpha·r, preference p=1 on observed.
        # A = G + Σ (c-1)·f fᵀ (G = FᵀF baseline over *all* items),
        # b = Σ c·f on observed entries.
        wa = alpha * values * valid              # c - 1, 0 at padding
        wb = (wa + 1.0) * valid
    else:
        wa = valid
        wb = values * valid
    A, b = _lhs_fn(fixed, indices, wa, wb, bf16=bf16)
    if implicit:
        # G is added AFTER the einsum output on purpose: the
        # blocks' normal-equation build has no data dependence on the
        # fixed-side Gramian, so its (mesh) all-reduce overlaps the
        # first block's gather instead of gating it
        A = G[None, None] + A

    reg_n = reg * jnp.maximum(counts.astype(jnp.float32), 1.0) if scale_reg \
        else jnp.full(counts.shape, reg, dtype=jnp.float32)
    A = A + reg_n[..., None, None] * jnp.eye(r, dtype=A.dtype)
    return solve_spd_batch(A, b, mesh=mesh)


#: Implicit-path baseline Gramian FᵀF of the fixed side: the plain
#: einsum, whose collective GSPMD derives under a mesh. Jitted
#: (compile-once) for the eager split path; inlined when traced inside
#: a half-step program.
_fixed_gramian = jax.jit(gramian)


@functools.partial(jax.jit, static_argnames=("implicit", "bf16"),
                   donate_argnums=(5, 6))
def _partials_block(fixed: jax.Array, indices: jax.Array,
                    values: jax.Array, counts: jax.Array,
                    row_ids: jax.Array, A_acc: jax.Array,
                    b_acc: jax.Array, alpha: float, implicit: bool,
                    bf16: bool = False):
    """Split-mode half of :func:`_update_block`: per-VIRTUAL-row partials
    Σ w·ffᵀ and Σ w·f, scatter-added onto the owning real rows.
    Sentinel/padding virtual rows contribute exactly zero (their valid
    mask is all-zero), so out-of-range ids are safe under mode="drop"."""
    r = fixed.shape[-1]
    L = indices.shape[-1]
    valid = (jnp.arange(L)[None, None, :]
             < counts[:, :, None]).astype(jnp.float32)
    if implicit:
        wa = alpha * values * valid
        wb = (wa + 1.0) * valid
    else:
        wa = valid
        wb = values * valid
    A_v, b_v = _lhs_fn(fixed, indices, wa, wb, bf16=bf16)
    ids = row_ids.reshape(-1)
    A_acc = A_acc.at[ids].add(A_v.reshape(-1, r, r), mode="drop")
    b_acc = b_acc.at[ids].add(b_v.reshape(-1, r), mode="drop")
    return A_acc, b_acc


@functools.partial(jax.jit, static_argnames=("implicit", "scale_reg",
                                             "mesh"))
def _solve_accumulated(A_acc: jax.Array, b_acc: jax.Array,
                       G, real_counts: jax.Array, reg: float,
                       implicit: bool, scale_reg: bool,
                       mesh: Optional[Mesh] = None) -> jax.Array:
    """Finish a split-mode half-step: implicit baseline Gramian (added
    once per real row, after accumulation), ALS-WR regularization from
    TRUE row totals, one batched SPD solve. Rows with no ratings keep
    b=0 and solve to exactly 0 — identical to the pad path's padding."""
    r = A_acc.shape[-1]
    A = A_acc + G[None] if implicit else A_acc
    reg_n = reg * jnp.maximum(real_counts.astype(jnp.float32), 1.0) \
        if scale_reg else jnp.full(real_counts.shape, reg,
                                   dtype=jnp.float32)
    A = A + reg_n[:, None, None] * jnp.eye(r, dtype=A.dtype)
    return solve_spd_batch(A, b_acc, mesh=mesh)


_zeros_factories: dict = {}


def _zeros_sharded(shape, mesh: Optional[Mesh], spec: P) -> jax.Array:
    """Device-side zeros with the right sharding, via a cached compiled
    factory — a fresh ``jax.jit(lambda)`` per call would re-trace (and
    re-compile) the allocation on every half-iteration."""
    key = (shape, mesh, spec if mesh is not None else None)
    fn = _zeros_factories.get(key)
    if fn is None:
        if mesh is None:
            fn = jax.jit(lambda: jnp.zeros(shape, jnp.float32))
        else:
            fn = jax.jit(lambda: jnp.zeros(shape, jnp.float32),
                         out_shardings=NamedSharding(mesh, spec))
        _zeros_factories[key] = fn
    return fn()


def _update_side_split(fixed: jax.Array, sh: dict, params: "ALSParams",
                       block_rows: int) -> jax.Array:
    """One half-iteration in split mode. Accumulators live row-sharded
    like the factors; virtual-row blocks bound the [B, L, r] gather temp
    exactly as the pad path does."""
    implicit = params.implicit_prefs
    bf16 = params.matmul_dtype == "bfloat16"
    G = _fixed_gramian(fixed) if implicit else None
    gsrc = fixed.astype(jnp.bfloat16) \
        if params.gather_dtype == "bfloat16" else fixed
    d, n_vper, L = sh["idx"].shape
    n_pad = sh["real_cnt"].shape[0]
    r = fixed.shape[-1]
    A_acc = _zeros_sharded((n_pad, r, r), sh["mesh"],
                           rows_spec(sh["mesh"]))
    b_acc = _zeros_sharded((n_pad, r), sh["mesh"], rows_spec(sh["mesh"]))
    for s in range(0, n_vper, block_rows):
        e = min(s + block_rows, n_vper)
        A_acc, b_acc = _partials_block(
            gsrc, sh["idx"][:, s:e], sh["val"][:, s:e],
            sh["cnt"][:, s:e], sh["rid"][:, s:e], A_acc, b_acc,
            params.alpha, implicit, bf16=bf16)
    if G is None:
        G = jnp.zeros((r, r), jnp.float32)  # static arg shape filler
    return _solve_accumulated(A_acc, b_acc, G, sh["real_cnt"], params.reg,
                              implicit, params.scale_reg_by_count,
                              mesh=sh["mesh"])


def _bucket_half_impl(fixed: jax.Array, out0: jax.Array, buckets,
                      reg, alpha, implicit: bool, scale_reg: bool,
                      bf16: bool, block_rows_opt,
                      gather_bf16: bool = False,
                      mesh: Optional[Mesh] = None) -> jax.Array:
    """Trace-level body of a bucketed half-iteration (jit-wrapped by
    :func:`_bucket_half_step` and inlined whole-training by
    :func:`_train_bucket_fused`)."""
    r = fixed.shape[-1]
    G = _fixed_gramian(fixed) if implicit else None
    # the bf16 shadow (ALSParams.gather_dtype): gram/rhs/solve stay f32.
    # The barrier shares ONE materialized shadow across every bucket's
    # gather instead of letting XLA re-fuse the cast per bucket
    # (measured ≈ neutral on the 20M bench but keeps the shadow a
    # single buffer)
    gsrc = jax.lax.optimization_barrier(
        fixed.astype(jnp.bfloat16)) if gather_bf16 else fixed
    out = out0
    for b in buckets:
        d, n_per, L = b["idx"].shape
        block = block_rows_opt or _auto_block_rows(n_per, L, r)
        parts = []
        for s in range(0, n_per, block):
            e = min(s + block, n_per)
            parts.append(_update_block(
                gsrc, G, b["idx"][:, s:e], b["val"][:, s:e],
                b["cnt"][:, s:e], reg, alpha, implicit, scale_reg,
                bf16=bf16, mesh=mesh))
        new = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                               axis=1)
        # each real row lives in exactly one bucket → unique indices (the
        # fast scatter regime; duplicate-index scatter-add serializes on
        # TPU); padding rows carry an out-of-range sentinel and drop
        out = out.at[b["rid"]].set(new.reshape(d * n_per, r),
                                   mode="drop", unique_indices=True)
    return out


@functools.partial(jax.jit,
                   static_argnames=("implicit", "scale_reg", "bf16",
                                    "block_rows_opt",
                                    "gather_bf16", "mesh"),
                   donate_argnums=(1,))
def _bucket_half_step(fixed: jax.Array, out0: jax.Array, buckets,
                      reg, alpha, *, implicit: bool, scale_reg: bool,
                      bf16: bool, block_rows_opt,
                      gather_bf16: bool = False,
                      mesh: Optional[Mesh] = None) -> jax.Array:
    """One ENTIRE bucketed half-iteration as a single compiled program —
    Gramian, every bucket's normal-equation blocks, solves, and the
    unique-index scatters all fuse into one dispatch, instead of one
    dispatch per bucket plus their unjitted slice ops.

    ``reg``/``alpha`` stay traced so hyperparameter sweeps reuse the
    compilation; the bucket STRUCTURE (shapes) is the cache key.
    """
    return _bucket_half_impl(fixed, out0, buckets, reg, alpha, implicit,
                             scale_reg, bf16, block_rows_opt,
                             gather_bf16, mesh)


def _update_side_bucket(fixed: jax.Array, bk: dict, params: "ALSParams"
                        ) -> jax.Array:
    """One half-iteration over a bucketed layout: per bucket, the same
    dense normal-equation update as the pad path (bucket counts ARE the
    true row totals — rows are never split). Contraction depth per
    bucket = its L, so every einsum feeds the MXU a deep K."""
    r = fixed.shape[-1]
    out0 = _zeros_sharded((bk["n_rows_padded"], r), bk["mesh"],
                          rows_spec(bk["mesh"]))
    return _bucket_half_step(
        fixed, out0, tuple(bk["buckets"]), params.reg, params.alpha,
        implicit=params.implicit_prefs,
        scale_reg=params.scale_reg_by_count,
        bf16=(params.matmul_dtype == "bfloat16"),
        block_rows_opt=params.block_rows,
        gather_bf16=(params.gather_dtype == "bfloat16"),
        mesh=bk["mesh"])


def _pad_half_impl(fixed: jax.Array, lay: dict, block: int, reg, alpha,
                   implicit: bool, scale_reg: bool, bf16: bool,
                   gather_bf16: bool = False,
                   mesh: Optional[Mesh] = None) -> jax.Array:
    """One pad-layout half-iteration (trace-level body): Gramian, row
    blocks through :func:`_update_block`, flat reshape. SHARED by the
    per-step path (:func:`_update_side`) and the fused whole-run
    trainer — the two must never diverge."""
    G = _fixed_gramian(fixed) if implicit else None
    gsrc = jax.lax.optimization_barrier(
        fixed.astype(jnp.bfloat16)) if gather_bf16 else fixed
    d, n_per, L = lay["idx"].shape
    parts = []
    for st in range(0, n_per, block):
        e = min(st + block, n_per)
        parts.append(_update_block(
            gsrc, G, lay["idx"][:, st:e], lay["val"][:, st:e],
            lay["cnt"][:, st:e], reg, alpha, implicit, scale_reg,
            bf16=bf16, mesh=mesh))
    out = parts[0] if len(parts) == 1 \
        else jnp.concatenate(parts, axis=1)
    return out.reshape(d * n_per, out.shape[-1])


@functools.partial(jax.jit,
                   static_argnames=("implicit", "scale_reg", "bf16",
                                    "kind_u", "kind_i",
                                    "block_u", "block_i",
                                    "block_rows_opt", "nu", "ni",
                                    "shard_u", "shard_i",
                                    "gather_bf16"))
def _train_fused(U: jax.Array, V: jax.Array, lay_u, lay_i, reg, alpha,
                 iters, *, implicit: bool, scale_reg: bool, bf16: bool,
                 kind_u: str, kind_i: str, block_u: int,
                 block_i: int, block_rows_opt, nu: int, ni: int,
                 shard_u, shard_i,
                 gather_bf16: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The WHOLE training run as ONE compiled program (no
    checkpointing) instead of 2·iters·blocks dispatches. Each side's
    half-step is chosen
    STATICALLY by its layout kind ("pad" or "bucket" — mixed sides are a
    normal history_mode='auto' outcome on skewed data), both realized by
    the same impls the per-step path uses. ``iters`` stays traced (a
    sweep over iteration counts shares one compilation); ``shard_*`` are
    NamedShardings (hashable, static) constraining each half-step's
    output on a mesh."""

    def half(fixed, kind, lay, block, n_total, shard):
        mesh = None if shard is None else shard.mesh
        if kind == "bucket":
            out0 = jnp.zeros((n_total, fixed.shape[-1]), fixed.dtype)
            if shard is not None:
                out0 = jax.lax.with_sharding_constraint(out0, shard)
            return _bucket_half_impl(fixed, out0, lay, reg, alpha,
                                     implicit, scale_reg, bf16,
                                     block_rows_opt, gather_bf16, mesh)
        out = _pad_half_impl(fixed, lay, block, reg, alpha, implicit,
                             scale_reg, bf16, gather_bf16, mesh)
        if shard is not None:
            out = jax.lax.with_sharding_constraint(out, shard)
        return out

    def body(_, UV):
        U, V = UV
        U = half(V, kind_u, lay_u, block_u, nu, shard_u)
        V = half(U, kind_i, lay_i, block_i, ni, shard_i)
        return U, V

    # fori_loop, not Python unrolling: program size must not scale with
    # num_iterations (a 200-iteration run would otherwise inline 400
    # half-steps into one XLA program)
    return jax.lax.fori_loop(0, iters, body, (U, V))


def _update_side(fixed: jax.Array, indices: jax.Array, values: jax.Array,
                 counts: jax.Array, params: "ALSParams",
                 block_rows: int,
                 mesh: Optional[Mesh] = None) -> jax.Array:
    """One half-iteration, row-blocked to bound the [B, L, r] gather's
    memory (ALX-style batched updates); the per-step twin of the fused
    trainer — both route through :func:`_pad_half_impl`."""
    return _pad_half_impl(
        fixed, {"idx": indices, "val": values, "cnt": counts},
        block_rows, params.reg, params.alpha, params.implicit_prefs,
        params.scale_reg_by_count,
        bf16=(params.matmul_dtype == "bfloat16"),
        gather_bf16=(params.gather_dtype == "bfloat16"),
        mesh=mesh)


@functools.partial(jax.jit, static_argnames=("n", "n_padded", "rank"))
def _init_factors(key: jax.Array, n: int, n_padded: int, rank: int
                  ) -> jax.Array:
    """MLlib-style init: N(0,1)/sqrt(rank) for the real rows, zeros for
    padding — the draw depends only on ``n`` so results are identical for
    any mesh size, and zero padding rows stay exactly zero through updates
    (their b is 0) without polluting the implicit Gramian. Jitted: one
    dispatch, not one per op."""
    f = (jax.random.normal(key, (n, rank), dtype=jnp.float32)
         / jnp.sqrt(float(rank)))
    if n_padded > n:
        f = jnp.vstack([f, jnp.zeros((n_padded - n, rank), jnp.float32)])
    return f


def _shard(x, mesh: Optional[Mesh], spec: P):
    if mesh is None:
        # device_put, NOT jnp.asarray: asarray routes through the eager
        # op machinery — one blocking dispatch round trip per array (a
        # bucketed layout has ~90); device_put transfers asynchronously
        # (same dtype canonicalization)
        return jax.device_put(x)
    return jax.device_put(x, NamedSharding(mesh, spec))


_init_sharded_cache: dict = {}


def _init_factors_sharded(key: jax.Array, n: int, n_padded: int,
                          rank: int, mesh: Optional[Mesh]) -> jax.Array:
    """Factor init with the output DIRECTLY computed into the row
    sharding (jit ``out_shardings``) — under multi-controller JAX a
    plain jit output lands on the local default device and a host-side
    ``device_put`` to a cross-process sharding is not generally legal,
    so the sharding must come out of the compiled program itself."""
    if mesh is None:
        return _init_factors(key, n=n, n_padded=n_padded, rank=rank)
    ck = (tuple(mesh.devices.flat), mesh.axis_names)  # jit's static-arg
    fn = _init_sharded_cache.get(ck)                  # cache handles shapes
    if fn is None:
        fn = jax.jit(_init_factors.__wrapped__,
                     static_argnames=("n", "n_padded", "rank"),
                     out_shardings=NamedSharding(mesh, rows_spec(mesh)))
        _init_sharded_cache[ck] = fn
    return fn(key, n=n, n_padded=n_padded, rank=rank)


def _auto_block_rows(n_per: int, L: int, rank: int) -> int:
    """Per-device rows per update block, targeting ~1GB for the [B, L, r]
    f32 gather temp. Fewer, bigger blocks matter more than temp memory:
    each block is a separate dispatch, and measured on a v5e chip the
    half-step went 414M→2.4B ratings/s/iter moving 128MB→1GB (68→9
    dispatches); HBM comfortably holds the temp beside factors+histories."""
    budget = 1024 * 1024 * 1024
    b = max(64, budget // max(1, L * rank * 4))
    return min(n_per, b)


def _blocked(h: PaddedHistories, n_dev: int, mesh: Optional[Mesh]) -> dict:
    """Host → device: reshape [N, …] histories to the [n_dev, N/n_dev, …]
    blocked layout and shard the leading axis over all mesh devices, so
    every row block spans every device."""
    n_per = h.n_rows // n_dev
    spec = rows_spec(mesh)
    return {
        "idx": _shard(h.indices.reshape(n_dev, n_per, h.max_len), mesh, spec),
        "val": _shard(h.values.reshape(n_dev, n_per, h.max_len), mesh, spec),
        "cnt": _shard(h.counts.reshape(n_dev, n_per), mesh, spec),
    }


def _blocked_split(sh: SplitHistories, n_dev: int,
                   mesh: Optional[Mesh]) -> dict:
    """Split-mode device layout: virtual-row arrays blocked like
    :func:`_blocked`; real-row accumulator metadata stays flat+sharded."""
    n_vper = sh.n_virtual // n_dev
    spec = rows_spec(mesh)
    return {
        "mode": "split",
        "mesh": mesh,
        "idx": _shard(sh.indices.reshape(n_dev, n_vper, sh.max_len),
                      mesh, spec),
        "val": _shard(sh.values.reshape(n_dev, n_vper, sh.max_len),
                      mesh, spec),
        "cnt": _shard(sh.counts.reshape(n_dev, n_vper), mesh, spec),
        "rid": _shard(sh.row_ids.reshape(n_dev, n_vper), mesh, spec),
        "real_cnt": _shard(sh.real_counts, mesh, spec),
    }


def _blocked_bucket(bh: BucketedHistories, n_dev: int,
                    mesh: Optional[Mesh]) -> dict:
    """Bucketed device layout. Buckets with at least one row per device
    shard the ROW axis (like the pad path); skinnier buckets (the few
    mega-popular rows) shard the L axis instead — their normal-equation
    einsum contracts over L, which GSPMD turns into per-device partial
    Gramians + an all-reduce, so even a single 10M-entry row spreads
    across the mesh."""
    spec_rows = rows_spec(mesh)
    all_axes = None if mesh is None else tuple(mesh.axis_names)
    buckets = []
    for b in bh.buckets:
        n_bk, L = b.indices.shape
        # count REAL rows (padding carries the sentinel): a bucket with
        # fewer real rows than devices would leave most of the mesh
        # holding padding under row sharding
        n_real = int((np.asarray(b.row_ids) < bh.n_rows_padded).sum())
        if n_real >= n_dev or L % n_dev != 0:
            shape = (n_dev, n_bk // n_dev, L)
            spec = spec_rows
            cnt_spec = spec_rows
        else:  # row-axis thinner than the mesh: shard the history axis
            shape = (1, n_bk, L)
            spec = P(None, None, all_axes)
            cnt_spec = P(None, None)
        buckets.append({
            "idx": _shard(b.indices.reshape(shape), mesh, spec),
            "val": _shard(b.values.reshape(shape), mesh, spec),
            "cnt": _shard(b.counts.reshape(shape[:2]), mesh, cnt_spec),
            "rid": _shard(b.row_ids, mesh,
                          spec_rows if b.row_ids.shape[0]
                          % n_dev == 0 else P(None)),
        })
    return {
        "mode": "bucket",
        "mesh": mesh,
        "buckets": buckets,
        "n_rows_padded": bh.n_rows_padded,
    }


def auto_split_len(counts: np.ndarray) -> int:
    """Pick the split-mode padded length: the power-of-two L in [32, 8192]
    minimizing total padded entries Σ ⌈c/L⌉·L (padding waste vs
    virtual-row count both fall out of this objective; ties → larger L =
    fewer scatter rows)."""
    best_L, best_total = 32, None
    c = counts[counts > 0]
    if c.size == 0:
        return 32
    for p in range(5, 14):  # 32 .. 8192
        L = 1 << p
        total = int((-(-c // L) * L).sum())
        if best_total is None or total <= best_total:
            best_L, best_total = L, total
    return best_L


def _pack(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
          n_rows: int, params: "ALSParams", n_dev: int):
    """History packing for one side; the sort/scatter runs on device
    (host numpy packing costs ~10s at MovieLens-20M scale — hard part 2
    of SURVEY §7 is exactly this host round-trip, so it's eliminated).
    Layout choice (``history_mode``): pad when nothing would drop, split
    when skew would otherwise truncate entries (drop-free, MLlib parity).
    """
    from ..ops.ragged import (
        AUTO_CAP_ENTRIES,
        pack_histories_bucketed_device,
        pack_histories_device,
        pack_histories_split_device,
        resolve_max_len,
    )

    max_history = params.max_history
    mode = params.history_mode
    counts = None
    if mode == "auto":
        if max_history is not None:
            mode = "pad"  # an explicit cap keeps round-1 semantics
        else:
            counts = np.bincount(rows, minlength=n_rows)
            L_full = int(counts.max(initial=1))
            slots = n_rows * L_full
            # pad must fit the absolute cap AND not waste HBM: at skew,
            # rows padded to the longest history can blow memory by 30x+
            # (measured: a 5%-sample eval fold padded 0.5M entries into
            # 33M slots per side — RESOURCE_EXHAUSTED on the device).
            # The bucketed layout bounds waste at ~2x.
            dense_enough = slots <= max(4 * len(rows), 1_000_000)
            mode = "pad" if (slots <= AUTO_CAP_ENTRIES
                             and dense_enough) else "bucket"
    if mode == "bucket":
        return pack_histories_bucketed_device(
            rows, cols, vals, n_rows, pad_rows_to=n_dev,
            max_len=None if max_history is None else int(max_history),
            counts=counts)
    if mode == "split":
        import warnings

        warnings.warn(
            "history_mode='split' scatter-adds duplicate row indices, "
            "which TPUs serialize — measured ~5x slower than 'bucket' "
            "at MovieLens-20M scale (before PR 1). 'bucket' is the "
            "drop-free layout of choice; 'split' is kept for "
            "comparison runs.", UserWarning, stacklevel=3)
        if counts is None:
            counts = np.bincount(rows, minlength=n_rows)
        L = int(max_history) if max_history is not None \
            else auto_split_len(counts)
        return pack_histories_split_device(rows, cols, vals, n_rows,
                                           max(L, 1), pad_rows_to=n_dev)
    if max_history is not None:
        L = int(max_history)
    else:
        counts = np.bincount(rows, minlength=n_rows) if counts is None \
            else counts
        L = resolve_max_len(counts, n_rows, None)
    return pack_histories_device(rows, cols, vals, n_rows, max(L, 1),
                                 pad_rows_to=n_dev)


@dataclass
class PackedRatings:
    """Packed histories for both sides plus a cache of their blocked
    device layouts. ``train_als`` per-call work on a pre-packed problem is
    then just the compiled update dispatches — re-deriving the blocked
    reshape/shard layout every call means repeated host→device
    transfers, which dwarf the compute in sweeps.

    Duck-compatible with the former ``(user_h, item_h)`` tuple return of
    :func:`pack_ratings` (iteration and indexing)."""

    user_h: object
    item_h: object
    mesh: Optional[Mesh] = None
    #: real (unpadded) problem dims — lets ``train_als(None, packed=...)``
    #: run without the host holding any RatingsCOO (multi-host partial
    #: reads feed shards straight from storage)
    n_users: Optional[int] = None
    n_items: Optional[int] = None
    _blocked: dict = field(default_factory=dict, repr=False)
    _lock: object = field(default_factory=threading.Lock, repr=False)

    def __iter__(self):
        return iter((self.user_h, self.item_h))

    def __getitem__(self, i: int):
        return (self.user_h, self.item_h)[i]

    def blocked(self, side: str, n_dev: int, mesh: Optional[Mesh]) -> dict:
        key = (side, n_dev, None if mesh is None else tuple(mesh.devices.flat))
        # compute-once under the lock: parallel sweeps hit the same
        # layout from several threads, and re-deriving it means repeated
        # device transfers
        with self._lock:
            out = self._blocked.get(key)
            if out is None:
                h = self.user_h if side == "user" else self.item_h
                if isinstance(h, BucketedHistories):
                    out = _blocked_bucket(h, n_dev, mesh)
                elif isinstance(h, SplitHistories):
                    out = _blocked_split(h, n_dev, mesh)
                else:
                    out = _blocked(h, n_dev, mesh)
                self._blocked[key] = out
        return out


def pack_ratings(ratings: RatingsCOO, params: ALSParams,
                 mesh: Optional[Mesh] = None) -> PackedRatings:
    """Pre-pack both sides' histories for :func:`train_als`.

    Packing ships the COO to the device once; hyperparameter sweeps (and
    benchmarks) should pack once and pass ``packed=`` to every
    ``train_als`` call so retrains skip the transfer + sort. Under a
    multi-controller runtime this routes to
    :func:`pack_ratings_multihost` (per-process device feeding)."""
    enable_compilation_cache()
    if mesh is not None and jax.process_count() > 1:
        return pack_ratings_multihost(ratings, params, mesh)
    if hasattr(ratings, "to_coo"):  # a sharded source on one host
        ratings = ratings.to_coo()
    n_dev = 1 if mesh is None else mesh.devices.size
    user_h = _pack(ratings.users, ratings.items, ratings.ratings,
                   ratings.n_users, params, n_dev)
    item_h = _pack(ratings.items, ratings.users, ratings.ratings,
                   ratings.n_items, params, n_dev)
    return PackedRatings(user_h=user_h, item_h=item_h, mesh=mesh,
                         n_users=ratings.n_users, n_items=ratings.n_items)


#: id(ratings) → (weakref-to-ratings, per-ratings ComputeOnce). The pack
#: depends on params only through the layout knobs (history_mode,
#: max_history) and the mesh — NOT rank/reg/alpha/iterations — so an
#: eval sweep over algorithm hyperparameters re-uses one packing per
#: fold (VERDICT r1 task 7: sweeps re-paid the COO ship + sort every
#: retrain).
_pack_cache: dict = {}
_pack_cache_lock = threading.Lock()


def pack_ratings_cached(ratings: RatingsCOO, params: ALSParams,
                        mesh: Optional[Mesh] = None) -> PackedRatings:
    """Memoizing :func:`pack_ratings`: keyed by the identity of the
    ratings object and the packing-relevant params. Compute-once across
    threads (a parallel sweep's workers all miss together during the
    long transfer-and-sort window otherwise; failed packs retry);
    entries die with the ratings object (weakref callback), so folds
    don't pin device memory past their evaluation."""
    import weakref

    from ..utils.memo import ComputeOnce

    with _pack_cache_lock:
        ent = _pack_cache.get(id(ratings))
        if ent is None or ent[0]() is not ratings:
            rid = id(ratings)
            ref = weakref.ref(ratings,
                              lambda _, i=rid: _pack_cache.pop(i, None))
            ent = _pack_cache[rid] = (ref, ComputeOnce(retry_on_failure=True))
        memo = ent[1]
    key = (params.max_history, params.history_mode,
           None if mesh is None else tuple(mesh.devices.flat))
    return memo.get(key, lambda: pack_ratings(ratings, params, mesh))


def pack_ratings_multihost(ratings, params: ALSParams,
                           mesh: Mesh, force: bool = False
                           ) -> PackedRatings:
    """Multi-controller packing (``jax.process_count() > 1``): every
    process packs ONLY the history rows its local devices own and the
    global blocked arrays are assembled from per-process shards
    (``jax.make_array_from_process_local_data`` — the Spark-executor
    feeding role, SURVEY §2.3). Single-process falls through to
    :func:`pack_ratings`.

    v2 contract (partial reads): ``ratings`` may be a *sharded source*
    (``read_rows``/``row_counts`` — e.g.
    :class:`~predictionio_tpu.models.data.ColumnarRatingsSource` over a
    shared-filesystem columnar sidecar), in which case each process
    MATERIALIZES only the rating triples of its own row range — the
    ``JDBCPEvents.scala:49-89`` partitioned-read role. A plain
    :class:`RatingsCOO` (every host already holding the global COO)
    still works.

    Layouts: "auto" resolves per side like the single-host pack — pad
    when nothing would drop, otherwise the DROP-FREE bucketed layout,
    whose per-bucket rows are padded to the device count and sharded so
    each process packs only its own bucket rows ("split" maps to bucket
    here: its duplicate-index scatter has no multihost layout).
    """
    import jax

    from ..ops.ragged import pack_histories, resolve_max_len

    if jax.process_count() == 1 and not force:
        return pack_ratings(ratings, params, mesh)

    n_dev = mesh.devices.size
    flat = list(mesh.devices.flat)
    pid = jax.process_index()
    mine = [i for i, d in enumerate(flat) if d.process_index == pid]
    if not mine:
        raise ValueError(f"process {pid} owns no devices in the mesh; "
                         "build the mesh over every process's devices")
    if mine != list(range(mine[0], mine[-1] + 1)):
        raise ValueError("pack_ratings_multihost requires each process's "
                         "devices to be contiguous in mesh order")

    from ..ops.ragged import AUTO_CAP_ENTRIES

    is_source = hasattr(ratings, "read_rows")
    packed = PackedRatings(user_h=None, item_h=None, mesh=mesh,
                           n_users=ratings.n_users,
                           n_items=ratings.n_items)
    sides = {"user": ratings.n_users, "item": ratings.n_items}
    hs = {}
    for side, n_rows in sides.items():
        if is_source:
            counts = np.asarray(ratings.row_counts(side))
        else:
            rows_g = ratings.users if side == "user" else ratings.items
            counts = np.bincount(rows_g, minlength=n_rows)

        mode = params.history_mode
        bucket_cap = params.max_history and int(params.max_history)
        if mode == "split":
            # split's duplicate-index scatter has no multihost layout;
            # bucket covers its drop-free role. Split keeps EVERY entry
            # (its max_history is the virtual-row length, not a cap), so
            # the bucket stand-in must be uncapped too.
            mode = "bucket"
            bucket_cap = None
        elif mode == "auto":
            if params.max_history is not None:
                mode = "pad"
            else:
                L_full = int(counts.max(initial=1))
                mode = "pad" if n_rows * L_full <= AUTO_CAP_ENTRIES \
                    else "bucket"
        if mode == "bucket":
            # drop-free layout, sharded per process (each packs only
            # the bucket rows its devices own)
            if is_source:
                def rrm(m, _side=side):
                    return ratings.read_row_mask(_side, m)
            else:
                rows_g = ratings.users if side == "user" \
                    else ratings.items
                cols_g = ratings.items if side == "user" \
                    else ratings.users

                def rrm(m, _r=rows_g, _c=cols_g):
                    sel = m[_r]
                    return _r[sel], _c[sel], ratings.ratings[sel]
            layout, h = _pack_side_bucket_multihost(
                rrm, counts, n_rows, mesh, mine, bucket_cap)
            packed._blocked[(side, n_dev,
                             tuple(mesh.devices.flat))] = layout
            hs[side] = h
            continue

        L = resolve_max_len(counts, n_rows,
                            params.max_history and int(params.max_history))
        n_pad = -(-n_rows // n_dev) * n_dev
        n_per = n_pad // n_dev
        start, stop = mine[0] * n_per, (mine[-1] + 1) * n_per
        if is_source:
            rows_l, cols_l, vals_l = ratings.read_rows(
                side, start, min(stop, n_rows))
        else:
            rows_g = ratings.users if side == "user" else ratings.items
            cols_g = ratings.items if side == "user" else ratings.users
            sel = (rows_g >= start) & (rows_g < min(stop, n_rows))
            rows_l, cols_l, vals_l = rows_g[sel], cols_g[sel], \
                ratings.ratings[sel]
        local = pack_histories(rows_l - start, cols_l, vals_l,
                               n_rows=stop - start, max_len=L,
                               pad_rows_to=1)
        d_loc = len(mine)
        sharding = NamedSharding(mesh, rows_spec(mesh))

        def glob(arr, tail_shape):
            return jax.make_array_from_process_local_data(
                sharding, arr.reshape((d_loc,) + tail_shape),
                (n_dev,) + tail_shape)

        blocked = {
            "idx": glob(local.indices, (n_per, L)),
            "val": glob(local.values, (n_per, L)),
            "cnt": glob(local.counts, (n_per,)),
        }
        key = (side, n_dev, tuple(mesh.devices.flat))
        packed._blocked[key] = blocked
        # n_rows/max_len drive factor sizing, _auto_block_rows and the
        # flops model; the host-side padded matrices never exist globally
        hs[side] = _LayoutOnlyHistories(n_rows=n_pad, max_len=L)
    packed.user_h = hs["user"]
    packed.item_h = hs["item"]
    return packed


@dataclass(frozen=True)
class _LayoutOnlyHistories:
    """Shape metadata standing in for a PaddedHistories whose blocked
    device arrays were assembled directly from per-process shards (the
    host-side padded matrices never exist globally)."""

    n_rows: int
    max_len: int


@dataclass(frozen=True)
class _LayoutOnlyBucket:
    length: int
    n_rows: int  # padded member rows


@dataclass(frozen=True)
class _LayoutOnlyBucketed:
    """Shape metadata standing in for a BucketedHistories assembled from
    per-process shards (duck-typed: padded_entries/n_rows_padded drive
    the FLOP model and factor sizing)."""

    buckets: tuple  # of _LayoutOnlyBucket
    n_rows: int
    n_rows_padded: int

    @property
    def padded_entries(self) -> int:
        return sum(b.n_rows * b.length for b in self.buckets)

    @property
    def max_len(self) -> int:
        return max((b.length for b in self.buckets), default=1)


def _pack_side_bucket_multihost(read_row_mask, counts: np.ndarray,
                                n_rows: int, mesh: Mesh, mine: list,
                                max_len: Optional[int]):
    """One side of the DROP-FREE multihost packing: every process
    derives the same global bucket plan from the same ``counts``, packs
    ONLY the bucket rows its devices own (an arbitrary row set — bucket
    membership is by history length), and returns per-bucket local
    arrays ready for ``jax.make_array_from_process_local_data``.

    Unlike the single-host layout, skinny buckets also shard by rows
    (L-axis sharding would split single rows' entries across processes
    by position); their padding rows solve to zero and drop."""
    import jax

    from ..ops.ragged import bucket_layout
    from ..ops.ragged import _pack_flat_on_device as pack_flat

    n_dev = mesh.devices.size
    d_loc = len(mine)
    if max_len is not None:
        counts = np.minimum(counts, int(max_len))
    plan, _, _ = bucket_layout(counts, min_len=8, pad_rows_to=n_dev,
                               max_len=None)
    n_rows_pad = max(-(-n_rows // n_dev) * n_dev, n_dev)

    # local destination map: global row -> offset in THIS process's flat
    # buffer (only rows this process owns; others stay -1)
    local_base = np.full(n_rows, -1, dtype=np.int64)
    owned = np.zeros(n_rows, dtype=bool)
    spans = []  # (L, rows_local, n_loc_slots, off_loc, rid_local)
    off_loc = 0
    for L, rows_k, n_bk_pad, _ in plan:
        npb = n_bk_pad // n_dev
        lo, hi = mine[0] * npb, (mine[-1] + 1) * npb
        rows_local = rows_k[lo:min(hi, len(rows_k))]
        n_loc = d_loc * npb
        rid_global = (n_rows_pad
                      + np.arange(n_bk_pad, dtype=np.int64)
                      - len(rows_k)).astype(np.int32)
        rid_global[:len(rows_k)] = rows_k
        local_base[rows_local] = off_loc + np.arange(
            len(rows_local), dtype=np.int64) * int(L)
        owned[rows_local] = True
        spans.append((int(L), rows_local, n_loc, off_loc,
                      rid_global[lo:hi]))
        off_loc += n_loc * int(L)
    S_loc = off_loc
    if S_loc >= 2 ** 31:  # pragma: no cover — >1B padded slots/process
        raise ValueError(
            f"bucketed multihost layout needs {S_loc} local slots "
            f"(> int32); use more processes or cap max_history")

    rows_l, cols_l, vals_l = read_row_mask(owned)
    flat_idx, flat_val = pack_flat(
        jnp.asarray(rows_l, dtype=jnp.int32),
        jnp.asarray(cols_l, dtype=jnp.int32),
        jnp.asarray(vals_l, dtype=jnp.float32),
        jnp.asarray(local_base, dtype=jnp.int32),
        jnp.asarray(counts, dtype=jnp.int32),
        n_rows=n_rows, S=max(S_loc, 1))
    flat_idx = np.asarray(flat_idx)
    flat_val = np.asarray(flat_val)

    sharding_rows = NamedSharding(mesh, rows_spec(mesh))
    sharding_cnt = NamedSharding(mesh, rows_spec(mesh))
    buckets = []
    layout_buckets = []
    for L, rows_local, n_loc, off, rid_local in spans:
        npb = n_loc // d_loc
        n_bk_pad = npb * n_dev
        idx_loc = flat_idx[off:off + n_loc * L].reshape(d_loc, npb, L)
        val_loc = flat_val[off:off + n_loc * L].reshape(d_loc, npb, L)
        cnt_loc = np.zeros(n_loc, dtype=np.int32)
        cnt_loc[:len(rows_local)] = counts[rows_local]
        buckets.append({
            "idx": jax.make_array_from_process_local_data(
                sharding_rows, idx_loc, (n_dev, npb, L)),
            "val": jax.make_array_from_process_local_data(
                sharding_rows, val_loc, (n_dev, npb, L)),
            "cnt": jax.make_array_from_process_local_data(
                sharding_cnt, cnt_loc.reshape(d_loc, npb),
                (n_dev, npb)),
            "rid": jax.make_array_from_process_local_data(
                sharding_rows, np.ascontiguousarray(rid_local),
                (n_bk_pad,)),
        })
        layout_buckets.append(_LayoutOnlyBucket(length=L,
                                                n_rows=n_bk_pad))
    layout = {"mode": "bucket", "mesh": mesh, "buckets": buckets,
              "n_rows_padded": n_rows_pad}
    h = _LayoutOnlyBucketed(buckets=tuple(layout_buckets),
                            n_rows=n_rows, n_rows_padded=n_rows_pad)
    return layout, h


def _layout_hist_lens(lay: dict) -> Tuple[int, ...]:
    """Padded history lengths (the L axis) of one side's blocked device
    layout."""
    if lay.get("mode") == "bucket":
        return tuple(int(b["idx"].shape[-1]) for b in lay["buckets"])
    return (int(lay["idx"].shape[-1]),)


def training_report(params: ALSParams, packed: "PackedRatings",
                    mesh: Optional[Mesh] = None) -> dict:
    """What a :func:`train_als` run over ``packed`` resolves to on the
    attached backend: the SPD solver variant and the layouts. One
    JSON-able dict — the train log line (``ptpu train`` prints it as
    ``Train kernels:``)."""
    from ..ops.solve import solver_variant

    n_dev = 1 if mesh is None else mesh.devices.size
    lays = {side: packed.blocked(side, n_dev, mesh)
            for side in ("user", "item")}
    lens = sorted({L for lay in lays.values()
                   for L in _layout_hist_lens(lay)})
    return {
        "rank": params.rank,
        "solver": solver_variant(params.rank),
        "gatherDtype": params.gather_dtype,
        "layout": {side: lay.get("mode", "pad")
                   for side, lay in lays.items()},
        "historyLens": lens,
    }


def train_als(ratings: RatingsCOO, params: ALSParams,
              mesh: Optional[Mesh] = None,
              packed: Optional[Tuple[PaddedHistories, PaddedHistories]]
              = None,
              checkpoint_dir: Optional[str] = None,
              checkpoint_every: int = 0
              ) -> Tuple[jax.Array, jax.Array]:
    """Run ALS; returns (user_factors, item_factors) with padded rows.

    Under a mesh, factor matrices and histories are row-sharded over all
    devices; each half-iteration runs as row blocks whose collectives
    (Gramian all-reduce, cross-shard factor gathers) XLA derives from the
    shardings. ``packed`` (from :func:`pack_ratings` with the SAME params
    + mesh) skips history packing.

    With ``checkpoint_dir``, factors are checkpointed every
    ``checkpoint_every`` iterations and a restarted call resumes from
    the latest saved iteration (step-level resume, SURVEY §5 — the
    reference restarts training from scratch after any failure).
    """
    enable_compilation_cache()
    if ratings is None:
        # multi-host partial reads: the host never holds a global COO;
        # the packed layout carries the problem dims instead
        if not (isinstance(packed, PackedRatings)
                and packed.n_users and packed.n_items):
            raise ValueError(
                "train_als(ratings=None) needs packed=PackedRatings with "
                "n_users/n_items (from pack_ratings/_multihost)")
        if checkpoint_dir:
            raise ValueError(
                "checkpointing fingerprints the ratings content; pass "
                "the ratings (or use checkpoint_dir=None) ")
        n_users_real, n_items_real = packed.n_users, packed.n_items
    elif hasattr(ratings, "read_rows"):  # a sharded source
        if ratings.n_users == 0 or ratings.n_items == 0:
            raise ValueError("ALS requires a non-empty ratings matrix "
                             "(0 users/items in the source)")
        if checkpoint_dir:
            raise ValueError(
                "checkpointing fingerprints the ratings content; pass a "
                "RatingsCOO (source.to_coo()) when using checkpoint_dir")
        n_users_real, n_items_real = ratings.n_users, ratings.n_items
    else:
        if len(ratings.users) == 0 or ratings.n_users == 0 \
                or ratings.n_items == 0:
            raise ValueError("ALS requires a non-empty ratings matrix "
                             "(0 entries/users/items given)")
        n_users_real, n_items_real = ratings.n_users, ratings.n_items
    n_dev = 1 if mesh is None else mesh.devices.size
    if packed is None:
        packed = pack_ratings(ratings, params, mesh)
    elif not isinstance(packed, PackedRatings):
        packed = PackedRatings(user_h=packed[0], item_h=packed[1], mesh=mesh)
    user_h, item_h = packed.user_h, packed.item_h

    u_split = isinstance(user_h, SplitHistories)
    i_split = isinstance(item_h, SplitHistories)
    # duck-typed: multihost bucket layouts stand in via
    # _LayoutOnlyBucketed, which also carries n_rows_padded
    u_rows_pad = getattr(user_h, "n_rows_padded", None) or user_h.n_rows
    i_rows_pad = getattr(item_h, "n_rows_padded", None) or item_h.n_rows

    ku, ki = jax.random.split(jax.random.key(params.seed))
    U = _init_factors_sharded(ku, n_users_real, u_rows_pad,
                              params.rank, mesh)
    V = _init_factors_sharded(ki, n_items_real, i_rows_pad,
                              params.rank, mesh)
    uh = packed.blocked("user", n_dev, mesh)
    ih = packed.blocked("item", n_dev, mesh)

    ckpt = None
    start = 0
    fingerprint = ""
    if checkpoint_dir:
        import hashlib
        import json as _json

        from ..workflow.checkpoint import (
            DistributedCheckpointer,
            make_checkpointer,
        )

        if checkpoint_every <= 0:
            checkpoint_every = 1  # a checkpoint dir implies checkpointing
        # refuse to resume from a different problem/params: fingerprint
        # everything that determines the factor trajectory
        # cheap content digest so a *different* dataset with identical
        # shape cannot silently resume from foreign factors: sample the
        # first/last 1024 COO triples (native dtype, no copies) plus
        # whole-array sums
        k = 1024
        content = hashlib.sha256()
        for arr in (np.asarray(ratings.users), np.asarray(ratings.items),
                    np.asarray(ratings.ratings)):
            content.update(np.ascontiguousarray(arr[:k]).tobytes())
            content.update(np.ascontiguousarray(arr[-k:]).tobytes())
            content.update(np.float64(arr.sum(dtype=np.float64)).tobytes())
        legacy_base = [
            params.rank, params.reg, params.alpha, params.implicit_prefs,
            params.seed, params.scale_reg_by_count, params.matmul_dtype,
            params.max_history,  # affects history truncation → trajectory
            ratings.n_users, ratings.n_items, len(ratings.users),
        ]
        base = legacy_base + [params.history_mode]
        if params.gather_dtype != "float32":
            # default-f32 fingerprints stay byte-identical to round-3
            # checkpoints; a bf16-shadow run has a different trajectory
            base = base + [params.gather_dtype]
        fingerprint = hashlib.sha256(_json.dumps(
            base + [content.hexdigest()]).encode()).hexdigest()[:16]
        # pre-content-digest dirs (round-1 scheme, no history_mode field)
        # stay resumable — but ONLY when this run resolved to round-1 pad
        # semantics on both sides: resuming a pad-trained checkpoint under
        # the new drop-free split layout would silently continue a
        # different objective
        accepted = (fingerprint,)
        if isinstance(user_h, PaddedHistories) \
                and isinstance(item_h, PaddedHistories):
            accepted += (hashlib.sha256(
                _json.dumps(legacy_base).encode()).hexdigest()[:16],)
        # multi-process runs get the preemption-safe distributed
        # container (per-process shard files + rendezvous commit,
        # ISSUE 11): every host writes only its local factor rows and
        # a kill -9 at any instant costs at most the step in flight
        ckpt = make_checkpointer(checkpoint_dir)
        meta = ckpt.get_metadata()
        if meta is not None \
                and meta.get("fingerprint") not in accepted:
            raise ValueError(
                f"checkpoint dir {checkpoint_dir} belongs to a different "
                f"ALS run (params/dataset/history-layout mismatch); use "
                f"a fresh dir")
        ckpt.set_metadata({"fingerprint": fingerprint})
        # resume from the newest RESTORABLE step within this run's
        # iteration budget — a torn step (crash mid-save) is skipped
        # and the walk falls back to the previous committed one
        start, state = ckpt.restore_latest(
            like={"U": U, "V": V}, max_step=params.num_iterations)
        if state is not None:
            if isinstance(ckpt, DistributedCheckpointer):
                # restore already reassembled + placed the local shards
                U, V = state["U"], state["V"]
            else:
                U = _shard(state["U"], mesh, rows_spec(mesh))
                V = _shard(state["V"], mesh, rows_spec(mesh))

    def _kind(h) -> str:
        if isinstance(h, (BucketedHistories, _LayoutOnlyBucketed)):
            return "bucket"
        if isinstance(h, (PaddedHistories, _LayoutOnlyHistories)):
            return "pad"
        return "split"

    kind_u, kind_i = _kind(user_h), _kind(item_h)
    if ckpt is None and "split" not in (kind_u, kind_i) \
            and start < params.num_iterations:
        # checkpoint-free runs compile the WHOLE training loop into one
        # dispatch, whatever mix of pad/bucket layouts auto resolved to
        shard = None if mesh is None \
            else NamedSharding(mesh, rows_spec(mesh))

        def _fused_args(kind, h, lay):
            if kind == "bucket":
                return tuple(lay["buckets"]), 0
            return lay, params.block_rows or _auto_block_rows(
                h.n_rows // n_dev, h.max_len, params.rank)

        lay_u, block_u = _fused_args(kind_u, user_h, uh)
        lay_i, block_i = _fused_args(kind_i, item_h, ih)
        return _train_fused(
            U, V, lay_u, lay_i, params.reg, params.alpha,
            params.num_iterations - start,
            implicit=params.implicit_prefs,
            scale_reg=params.scale_reg_by_count,
            bf16=(params.matmul_dtype == "bfloat16"),
            kind_u=kind_u, kind_i=kind_i,
            block_u=block_u, block_i=block_i,
            block_rows_opt=params.block_rows,
            nu=u_rows_pad, ni=i_rows_pad,
            shard_u=shard, shard_i=shard,
            gather_bf16=(params.gather_dtype == "bfloat16"))

    def _stepper(h, layout):
        if isinstance(h, (BucketedHistories, _LayoutOnlyBucketed)):
            return lambda fixed: _update_side_bucket(fixed, layout, params)
        n_r = h.n_virtual if isinstance(h, SplitHistories) else h.n_rows
        blk = params.block_rows or _auto_block_rows(
            n_r // n_dev, h.max_len, params.rank)
        if isinstance(h, SplitHistories):
            return lambda fixed: _update_side_split(fixed, layout, params,
                                                    blk)
        return lambda fixed: _update_side(
            fixed, layout["idx"], layout["val"], layout["cnt"], params,
            blk, mesh)

    step_u = _stepper(user_h, uh)
    step_i = _stepper(item_h, ih)

    try:
        for it in range(start, params.num_iterations):
            U = step_u(V)
            V = step_i(U)
            if ckpt is not None:
                ckpt.maybe_save(it + 1, {"U": U, "V": V},
                                every=checkpoint_every)
    finally:
        if ckpt is not None:
            ckpt.close()
    return U, V


def als_flops_per_iter(user_h, item_h, params: ALSParams) -> int:
    """Padded-work FLOP model for ONE full ALS iteration (both half-steps)
    under the given packed layout — the denominator-side of an honest MFU
    number: it counts the floating-point work the device is actually asked
    to do (including padding slots), not the nominal nnz·r² lower bound.

    Per half-step over ``padded`` = virtual-rows×L history slots and
    ``n_solve`` solve rows of rank r:
    A outer products 2·padded·r², b products 2·padded·r, fixed-side
    Gramian 2·rows_fixed·r² (implicit only), Cholesky r³/3 + two
    triangular solves 2r² per row."""
    r = params.rank

    def side(h, fixed_rows: int) -> int:
        if isinstance(h, (BucketedHistories, _LayoutOnlyBucketed)):
            padded = h.padded_entries
            n_solve = sum(b.n_rows for b in h.buckets)
        elif isinstance(h, SplitHistories):
            padded = h.n_virtual * h.max_len
            n_solve = h.n_rows_padded
        else:
            padded = h.n_rows * h.max_len
            n_solve = h.n_rows
        f = 2 * padded * r * r + 2 * padded * r
        if params.implicit_prefs:
            f += 2 * fixed_rows * r * r
        f += n_solve * (r ** 3 // 3 + 2 * r * r)
        return f

    def rows_of(h):
        # duck-typed: _LayoutOnlyBucketed carries n_rows_padded too
        return getattr(h, "n_rows_padded", None) or h.n_rows

    return side(user_h, rows_of(item_h)) + side(item_h, rows_of(user_h))


# -- row-quantized serving factor tables (ISSUE 13) --------------------------
#
# Tensor-Casting-style precision co-design (arXiv 2010.13100):
# recommendation factors tolerate low-precision STORAGE as long as the
# accumulation stays f32. Serving-side tables are therefore stored
# int8 (per-row absmax scales) or bf16 and dequantized on the fly —
# 4x (int8) / 2x (bf16) more users per HBM and the same factor less
# bandwidth per scored batch, with every dot product still
# accumulating in f32. Deploy-time only, like the mesh: a quantized
# table never enters the blob store.

#: the ServerConfig.serving_quant vocabulary
SERVING_QUANT_MODES = ("off", "bf16", "int8")

#: NDCG@10-vs-f32 floor the deploy-time parity probe enforces before a
#: quantized table may serve (:func:`quantize_serving_model` auto-off:
#: a model trained at a rank/scale where per-row int8 loses the
#: ranking falls back to f32 instead of silently degrading quality)
SERVING_QUANT_NDCG_FLOOR = 0.97


@jax.tree_util.register_dataclass
@dataclass
class QuantizedFactors:
    """A row-quantized serving factor table: ``data`` [n, r] int8 with
    per-row f32 absmax ``scale`` [n, 1], or bf16 with no scale. A
    pytree (so device placement, sharding and ``nbytes`` accounting
    reach the leaves); ``quant`` is static metadata. Serving paths
    dequantize after the wire — upcast + scale inside the compiled
    program, never as a materialized f32 copy of the table."""

    data: jax.Array = field(metadata=dict(static=False))
    scale: Optional[jax.Array] = field(default=None,
                                       metadata=dict(static=False))
    quant: str = field(default="int8", metadata=dict(static=True))

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.data.shape)

    @property
    def nbytes(self) -> int:
        nb = int(self.data.nbytes)
        if self.scale is not None:
            nb += int(self.scale.nbytes)
        return nb


def _table_leaves(t) -> Tuple[jax.Array, Optional[jax.Array]]:
    """(data, scale-or-None) of a factor table, quantized or plain."""
    if isinstance(t, QuantizedFactors):
        return t.data, t.scale
    return t, None


def table_quant(t) -> str:
    """The quant dtype of a factor table ("off" for plain f32)."""
    return t.quant if isinstance(t, QuantizedFactors) else "off"


def serving_quant_of(model) -> str:
    """The serving-quant realization of a bound model — the ``quant``
    label of the ``pio_serving_kernel`` info gauge."""
    return table_quant(getattr(model, "item_factors", model))


def _quantize_rows(rows: np.ndarray, quant: str
                   ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Host-side row quantization: per-row absmax scale → int8 in
    [-127, 127] (symmetric, so dequant is one multiply), or a bf16
    cast. Shared by :func:`quantize_serving_model` and the streaming
    hot-swap's re-quantization (:func:`apply_row_updates`)."""
    rows = np.asarray(rows, dtype=np.float32)
    if quant == "bf16":
        import ml_dtypes

        return rows.astype(ml_dtypes.bfloat16), None
    if quant != "int8":
        raise ValueError(f"quant must be 'bf16' or 'int8', got {quant!r}")
    amax = np.max(np.abs(rows), axis=-1, keepdims=True) \
        if rows.size else np.zeros((rows.shape[0], 1), np.float32)
    scale = np.maximum(amax, 1e-12).astype(np.float32) / 127.0
    data = np.clip(np.rint(rows / scale), -127, 127).astype(np.int8)
    return data, scale


_dequant_scaled = jax.jit(lambda d, s: d.astype(jnp.float32) * s)
_dequant_plain = jax.jit(lambda d: d.astype(jnp.float32))


def dequantize_table(t):
    """An f32 view of a factor table (identity for plain tables).
    Elementwise, so a row-sharded quantized table dequantizes into the
    same sharding. Used by the training-side consumers of a serving
    table (streaming fold-in solves) — the serving paths themselves
    dequantize inside their compiled programs instead."""
    if not isinstance(t, QuantizedFactors):
        return t
    if t.scale is None:
        return _dequant_plain(t.data)
    return _dequant_scaled(t.data, t.scale)


def table_host_f32(t) -> np.ndarray:
    """Host f32 copy of a factor table (plain or quantized, device or
    host resident) — the fold-in residual / parity-probe view."""
    if isinstance(t, QuantizedFactors):
        data = np.asarray(jax.device_get(t.data)).astype(np.float32)
        if t.scale is not None:
            data = data * np.asarray(jax.device_get(t.scale))
        return data
    if isinstance(t, np.ndarray):
        return np.asarray(t, dtype=np.float32)
    return np.asarray(jax.device_get(t)).astype(np.float32)


def _binary_ndcg(ranked, relevant, k: int) -> float:
    """Binary NDCG@k of one ranked id list against a relevant-id set
    (inlined rather than imported from controller.metric: models must
    not depend on the controller layer)."""
    dcg = sum(1.0 / np.log2(i + 2.0)
              for i, x in enumerate(ranked[:k]) if x in relevant)
    ideal = sum(1.0 / np.log2(i + 2.0)
                for i in range(min(k, len(relevant))))
    return float(dcg / ideal) if ideal else 0.0


def serving_quant_ndcg(U: np.ndarray, V: np.ndarray, qU, qV,
                       n_items: int, k: int = 10, sample: int = 32,
                       seed: int = 0) -> float:
    """Mean NDCG@k of the QUANTIZED ranking against the f32 ranking's
    top-k (f32 as ground truth) over a user sample — the deploy-time
    parity probe behind the auto-off fallback, and the same statistic
    the CI quality gate asserts on a fixture model."""
    n = min(sample, U.shape[0])
    if n == 0 or n_items == 0:
        return 1.0
    users = np.random.default_rng(seed).choice(U.shape[0], size=n,
                                               replace=False)
    kk = min(k, n_items)
    ids_f, _ = _host_topk(U[users], V, kk, n_items)
    ids_q, _ = _host_topk(table_host_f32(qU)[users],
                          table_host_f32(qV), kk, n_items)
    return float(np.mean([
        _binary_ndcg(list(a), set(b.tolist()), kk)
        for a, b in zip(ids_q, ids_f)]))


def quantize_serving_model(model: "ALSModel", quant: str, *,
                           parity_floor: float = SERVING_QUANT_NDCG_FLOOR,
                           parity_sample: int = 32, parity_k: int = 10,
                           seed: int = 0) -> "ALSModel":
    """A model whose serving factor tables are row-quantized to
    ``quant`` ("int8" | "bf16"; "off" returns the input) — the
    ``ServerConfig.serving_quant`` realization, applied at bind time
    BEFORE device placement so the host→HBM transfer already moves the
    small tables.

    Auto-off: before committing, a parity probe ranks ``parity_sample``
    users through both tables and requires NDCG@``parity_k`` ≥
    ``parity_floor`` against the f32 ranking; a model whose rank/scale
    cannot take the quantization keeps its f32 tables (logged), so
    ``--serving-quant`` can never silently degrade ranking. The CI
    quality gate (tests/test_serving_quant.py) asserts the same
    statistic on a fixture model."""
    import dataclasses

    if quant in (None, "", "off"):
        return model
    if quant not in ("bf16", "int8"):
        raise ValueError(
            f"serving quant must be one of {SERVING_QUANT_MODES}, "
            f"got {quant!r}")
    if isinstance(model.user_factors, QuantizedFactors):
        return model
    U = table_host_f32(model.user_factors)
    V = table_host_f32(model.item_factors)
    qU = QuantizedFactors(*_quantize_rows(U, quant), quant=quant)
    qV = QuantizedFactors(*_quantize_rows(V, quant), quant=quant)
    if parity_floor and parity_sample > 0:
        ndcg = serving_quant_ndcg(U, V, qU, qV, model.n_items,
                                  k=parity_k, sample=parity_sample,
                                  seed=seed)
        if ndcg < parity_floor:
            import logging

            logging.getLogger(__name__).warning(
                "serving_quant=%s parity probe failed (NDCG@%d %.4f "
                "< %.2f vs f32); keeping full-precision serving "
                "tables (auto-off)", quant, parity_k, ndcg,
                parity_floor)
            return model
    return dataclasses.replace(model, user_factors=qU, item_factors=qV)


# -- serving ----------------------------------------------------------------

#: items a chunk of :func:`_select_topk` holds. Set from a sweep on one
#: TPU v5e (PERF.md, finding 44.2: a dispatch of 32 rows over 4.85 M
#: items took 5.95 / 6.14 / 6.29 ms at 512 / 1024 / 2048, of 128 rows
#: 13.90 / 13.93 / 14.47, of 4 rows 3.44 / 3.41 / 3.42): the pass over
#: the scores reads the same bytes whatever the chunk, and the gather
#: and the last top-k grow with it.
SELECT_CHUNK = 512
_LANES = 128


def _chunk_maxima_kernel(scores: jax.Array, L: int, *,
                         interpret: bool = False) -> jax.Array:
    """``[B, ceil(n / L)]``: the maximum of each run of ``L`` scores of
    a row, the ragged tail as if it went on at ``-inf``. ONE pass over
    ``scores`` where it lies (no padded or re-laid-out copy: every
    plain-XLA form of this reduction copied ``[B, n]`` at some batch),
    eight rows and 128 chunks a grid step: a chunk's ``L / 128`` lane
    tiles fold elementwise, one lane reduction, one lane of the output
    tile. ``chunk_maxima`` in a device trace. The chip takes an ``L``
    of whole lane tiles; Pallas' interpreter (the tests) takes any."""
    from jax.experimental import pallas as pl

    B, n = scores.shape
    width = _LANES * L
    rows = min(B, 8)
    blocks = -(-n // width)

    def kernel(x_ref, o_ref):
        left = n - pl.program_id(1) * width  # real columns from here on
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, L), 1)

        def eight_chunks(g, out):
            for c in (8 * g + j for j in range(8)):
                x = x_ref[:, pl.ds(pl.multiple_of(c * L, L), L)]
                x = jnp.where(col < left - c * L, x, -jnp.inf)
                out = jnp.where(lane == c,
                                jnp.max(x, axis=1, keepdims=True), out)
            return out

        # a loop of sixteen, not 128 copies of the body: a program is
        # traced and lowered at every start of the process, once a
        # batch shape (the compile cache keeps only what comes after),
        # and 128 copies cost a third of a second each time
        o_ref[...] = jax.lax.fori_loop(
            0, _LANES // 8, eight_chunks,
            jnp.full((rows, _LANES), -jnp.inf, jnp.float32))

    out = pl.pallas_call(
        kernel, grid=(-(-B // rows), blocks),
        in_specs=[pl.BlockSpec((rows, width), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((rows, _LANES), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, blocks * _LANES), jnp.float32),
        interpret=interpret, name="chunk_maxima")(scores)
    return out[:, :-(-n // L)]


def _chunk_maxima(scores: jax.Array, L: int) -> jax.Array:
    """The chunk maxima on the backend in hand: the kernel on a TPU,
    the same reduction as plain ``jax.numpy`` elsewhere (a CPU has no
    tiled layout for a reshape to break; the tests hold the two to each
    other)."""
    B, n = scores.shape
    if jax.default_backend() == "tpu":
        return _chunk_maxima_kernel(scores, L)
    full, tail = divmod(n, L)
    maxima = scores[:, :full * L].reshape(B, full, L).max(axis=-1)
    if tail:
        maxima = jnp.concatenate(
            [maxima, scores[:, full * L:].max(axis=-1, keepdims=True)],
            axis=1)
    return maxima


def _select_topk(scores: jax.Array, k: int, *,
                 L: int = SELECT_CHUNK) -> Tuple[jax.Array, jax.Array]:
    """``jax.lax.top_k(scores, k)`` over ``[B, n]`` float32, exactly
    (values, indices, ties to the lowest index), without a selection
    over ``n``-wide rows:

    1. the maximum of each of a row's ``C = ceil(n / L)`` chunks of
       ``L`` consecutive scores (:func:`_chunk_maxima`);
    2. ``top_k`` of the maxima picks ``k`` chunks a row, and the picked
       chunk numbers are sorted ascending;
    3. ``top_k`` of those chunks' ``k * L`` scores, positions mapped
       back to indices.

    Every one of the true ``k`` lies in a picked chunk: were a winner
    ``e`` in an unpicked chunk ``c``, each of the ``k`` picked chunks
    would hold a score that beats ``e`` (a larger maximum, or an equal
    one in a chunk of lower number, hence at a lower index). With the
    picked chunks in ascending order a candidate's position ascends
    with its index, so the stable ``top_k`` of step 3 breaks ties as
    one ``top_k`` over the row would. The ragged last chunk is read as
    the row's last ``L`` scores and shifted to the window's front with
    ``-inf`` behind it: no copy of ``[B, n]`` is made, padded or not.

    The ONE parameter adapts to the shape when the program is traced:
    with no more chunks than ``k`` (``C <= k``: small catalogs, large
    ``k``) every chunk would be picked and the function is the single
    ``top_k``. ``L`` is a keyword for the tests' small arrays; nothing
    else passes it."""
    B, n = scores.shape
    if -(-n // L) <= k:
        return jax.lax.top_k(scores, k)
    full, tail = divmod(n, L)
    _, chunks = jax.lax.top_k(_chunk_maxima(scores, L), k)
    chunks = jnp.sort(chunks, axis=-1)
    # a chunk of a row is one [1, L] window of the scores as they lie;
    # the ragged one (number ``full``) comes as the row's LAST L scores
    ragged = chunks == full
    rows = jnp.broadcast_to(
        jnp.arange(B, dtype=chunks.dtype)[:, None], chunks.shape)
    picked = jax.lax.gather(
        scores,
        jnp.stack([rows, jnp.where(ragged, n - L, chunks * L)], axis=-1),
        jax.lax.GatherDimensionNumbers(
            offset_dims=(2,), collapsed_slice_dims=(0,),
            start_index_map=(0, 1)),
        slice_sizes=(1, L),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    if tail:
        # ... whose own ``tail`` scores end that window: to its front,
        # -inf behind them
        own = jnp.where(jnp.arange(L) < tail,
                        jnp.roll(picked, tail - L, axis=-1), -jnp.inf)
        picked = jnp.where(ragged[:, :, None], own, picked)
    values, at = jax.lax.top_k(picked.reshape(B, k * L), k)
    index = jnp.take_along_axis(chunks, at // L, axis=1) * L + at % L
    return values, index


@functools.partial(jax.jit, static_argnames=("k", "n_items"))
def _serve_topk(user_factors, item_factors, idx: jax.Array, *, k: int,
                n_items: int) -> Tuple[jax.Array, jax.Array]:
    """The WHOLE serving dispatch as one compiled program: user-row
    gather + [B, r]×[n_pad, r]ᵀ matmul + pad mask + the ``k`` best of a
    row (:func:`_select_topk`: exactly ``top_k``'s answer, in two
    stages wherever the catalog has more than ``k`` chunks of
    ``SELECT_CHUNK`` items, so nothing sorts an ``n_pad``-wide row).
    Eagerly these were 4-5 separate dispatches — fused, a query pays
    one dispatch and one fetch.

    Tables may be :class:`QuantizedFactors`: rows upcast to f32 (and
    per-row scales apply) INSIDE the program, so the dot accumulates
    f32 while HBM holds int8/bf16 — the serving-quant co-design."""
    ud, us = _table_leaves(user_factors)
    vd, vs = _table_leaves(item_factors)
    # the three scopes are metadata only (each operation's op_name in
    # a profile: docs/tracing.md); the compiled work is unchanged
    with jax.named_scope("pio_gather"):
        # ptpu: allow[materialized-gather] — a [B, r] serving row fetch
        # (no history axis): bounded by the micro-batcher's pow2 batch
        # cap
        vecs = ud[idx]
        if vecs.dtype != jnp.float32:
            vecs = vecs.astype(jnp.float32)
        if us is not None:
            # ptpu: allow[materialized-gather] — [B]-bounded scale fetch
            vecs = vecs * us.reshape(-1)[idx][:, None]
    with jax.named_scope("pio_score"):
        if vd.dtype != jnp.float32:
            vd = vd.astype(jnp.float32)
        scores = vecs @ vd.T
        if vs is not None:
            # per-row item scales factor out of the dot: score[b,i] =
            # (vec·q_i)·s_i — applied to the [B, n_pad] product, never
            # as a dequantized f32 copy of the table
            scores = scores * vs.reshape(1, -1)
        n_pad = vd.shape[0]
        mask = jnp.arange(n_pad) < n_items
        scores = jnp.where(mask[None, :], scores, -jnp.inf)
    with jax.named_scope("pio_select"):
        return _select_topk(scores, k)


def _device_topk(user_table, item_table, idx: np.ndarray, k_dev: int,
                 n_items: int) -> Tuple[jax.Array, jax.Array]:
    """The single-device batched top-k dispatch: :func:`_serve_topk`
    launched through :func:`aot.dispatch` — the seam that answers from
    a deserialized build-time executable when a warm artifact store is
    active (ISSUE 19), and is a plain tail call otherwise."""
    from .. import aot

    out = aot.dispatch(
        "serve_topk", _serve_topk, (user_table, item_table, idx),
        {"k": k_dev, "n_items": n_items})
    if _numerics.active():
        # debug_numerics: host NaN probe on the served scores (forces
        # the dispatch sync — the documented debug-mode cost);
        # nan_only because padded slots legitimately score -inf
        _numerics.check_array("serve_topk", out[0], nan_only=True)
    return out


#: serializes SHARDED serving dispatches process-wide. The mesh program
#: runs a collective (candidate all-gather) across every device: two
#: host threads enqueueing it concurrently can interleave their
#: per-device launches in different orders, and the collective
#: rendezvous deadlocks (observed as stuck AllGather participants on
#: the 8-device CPU mesh; the same launch-order hazard exists on real
#: meshes). The mesh is ONE resource — throughput comes from the
#: micro-batcher coalescing, not from concurrent mesh programs.
_mesh_dispatch_lock = threading.Lock()


def _is_row_sharded(arr) -> bool:
    """True when ``arr`` is a jax array whose rows are spread across
    more than one device (a :func:`shard_model` table) — its gathers
    must be GSPMD-resolved, never a host ``np.asarray``."""
    if isinstance(arr, QuantizedFactors):
        arr = arr.data
    sharding = getattr(arr, "sharding", None)
    if sharding is None:
        return False
    try:
        return len(sharding.device_set) > 1
    except Exception:  # noqa: BLE001 — exotic shardings: assume local
        return False


@functools.lru_cache(maxsize=16)
def _gather_rows_fn(mesh: Mesh):
    """Compile-once row gather from a row-sharded factor table to a
    REPLICATED [B, r] block: the GSPMD-inserted collective that
    resolves a cross-shard user-row fetch (the ALX serving gather).
    Output replicated so the per-shard ranking can consume it."""
    # ptpu: allow[materialized-gather] — [B, r] cross-shard row fetch
    # bounded by the serving batch; the sharded table itself never
    # materializes anywhere
    return jax.jit(lambda table, idx: table[idx],
                   out_shardings=NamedSharding(mesh, P()))


@functools.lru_cache(maxsize=16)
def _gather_vecs_fn(mesh: Mesh, has_scale: bool):
    """Quantized twin of :func:`_gather_rows_fn`: cross-shard row
    gather PLUS on-the-fly dequantization (upcast + per-row scale),
    output replicated — the int8/bf16 rows are what cross the ICI."""
    if has_scale:
        # ptpu: allow[materialized-gather] — [B, r] cross-shard row
        # fetch bounded by the serving batch (dequantized in-program)
        fn = (lambda table, scale, idx:
              table[idx].astype(jnp.float32) * scale[idx].reshape(-1, 1))
    else:
        # ptpu: allow[materialized-gather] — same [B, r] row fetch
        fn = lambda table, scale, idx: table[idx].astype(jnp.float32)
    return jax.jit(fn, out_shardings=NamedSharding(mesh, P()))


def _user_vecs(user_factors, user_indices: np.ndarray, mesh: Mesh):
    """[B, r] f32 query vectors for the sharded ranker, replicated over
    the mesh. Row-sharded tables gather via GSPMD collectives (the
    table never exists on one device) — quantized tables dequantize
    inside the same program; host/np tables gather locally. Host
    inputs stay UNCOMMITTED numpy so the mesh program places them
    itself — a ``jnp.asarray`` here would commit to device 0 and every
    dispatch would pay (and the transfer guard would flag) a
    device-to-device hop."""
    idx = np.asarray(user_indices, dtype=np.int64)
    ud, us = _table_leaves(user_factors)
    if _is_row_sharded(ud):
        if not isinstance(user_factors, QuantizedFactors):
            return _gather_rows_fn(mesh)(ud, idx)
        return _gather_vecs_fn(mesh, us is not None)(ud, us, idx)
    host = np.asarray(ud)[idx].astype(np.float32)
    if us is not None:
        host = host * np.asarray(us).reshape(-1)[idx][:, None]
    return host


def recommend_batch_sharded(user_factors, item_factors,
                            user_indices: np.ndarray, k: int,
                            mesh: Mesh, n_items: int
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Serving top-k over a device mesh — the multi-chip form of the
    reference's serving moment (``CreateServer.scala:508-510``): item
    factors ROW-SHARDED over every mesh device (a pod-scale catalog
    never lives on one chip), the query vectors replicated. User rows
    are first resolved — by a GSPMD-inserted collective gather when the
    user table is itself row-sharded (the >1-HBM regime), by a host
    gather otherwise. Each device then ranks its item shard locally
    ([B, n_local] matmul + local top_k) and the per-shard candidates
    are all-gathered and reduced to the global top-k — O(k·n_dev)
    cross-device traffic instead of O(n_items).

    Exact vs the single-device path for distinct scores (ties resolve
    by shard order rather than global index; float scores make exact
    ties measure-zero). Returns host (ids, scores) of shape [B, k].
    """
    n_dev = mesh.devices.size
    vd, _ = _table_leaves(item_factors)
    n_pad = vd.shape[0]
    if n_pad % n_dev:
        raise ValueError(f"item rows {n_pad} not divisible by mesh size "
                         f"{n_dev}; pad factors to a device multiple "
                         f"(shard_model does)")
    with _mesh_dispatch_lock:
        vecs = _user_vecs(user_factors, user_indices, mesh)
        # item_factors passes through UNPLACED when it is host data:
        # the mesh program shards it per in_specs; an eager jnp.asarray
        # would commit the whole table to device 0 first.
        ids, scores = _rank_sharded(mesh, vecs, item_factors, k,
                                    n_items)
        kk = min(k, n_items)
        ids, scores = jax.device_get((ids, scores))
    return ids[:, :kk], scores[:, :kk]


def _rank_sharded(mesh: Mesh, vecs, item_factors, k_dev: int,
                  n_items: int):
    """Launch the sharded ranking program for replicated [B, r] query
    vectors against a (possibly quantized) row-sharded item table —
    the shared entry of :func:`recommend_batch_sharded`,
    :func:`_dispatch_topk_chunk` and :func:`recommend_pinned`, through
    the compile-once cache. Callers hold ``_mesh_dispatch_lock``."""
    vd, vs = _table_leaves(item_factors)
    n_pad = vd.shape[0]
    k_local = min(k_dev, n_pad // mesh.devices.size)
    quant = table_quant(item_factors)
    ranked = _sharded_rank_fn(mesh, k_dev, k_local, n_items, quant)
    # ptpu: allow[callback-under-lock] — `ranked` is a compiled XLA
    # executable (jit of shard_map), not user code: it cannot re-enter
    # the dispatch lock, and serializing the launch is the lock's
    # entire purpose (concurrent mesh-collective launches deadlock)
    dyn = (vecs, vd) if vs is None else (vecs, vd, vs)
    # key_extra mirrors the _sharded_rank_fn cache key: the argument
    # signature alone cannot distinguish two mesh programs that differ
    # only in k/k_local
    from .. import aot
    return aot.dispatch(
        "sharded_rank", ranked, dyn,
        key_extra=(tuple(int(s) for s in mesh.devices.shape),
                   tuple(mesh.axis_names), k_dev, k_local, n_items,
                   quant or "off"))


@functools.lru_cache(maxsize=64)
def _sharded_rank_fn(mesh: Mesh, k: int, k_local: int, n_items: int,
                     quant: str = "off"):
    """Compile-once cache for the sharded serving program (a fresh
    closure per call would defeat the jit cache and recompile the mesh
    program on every serving batch). Keyed on (mesh, k, k_local,
    n_items, quant); shapes key the inner jit cache as usual. Axis
    names come from the mesh, so the same program serves a
    ``(data, model)`` training mesh and the ``(batch, model)`` serving
    mesh.

    Each shard ranks its LOCAL item rows (matmul + local top_k, with
    int8/bf16 rows dequantized in-program), then the per-shard
    candidates all-gather and reduce to the global top-k."""
    axes = tuple(mesh.axis_names)
    has_scale = quant == "int8"

    def local_rank(vecs, itf_local, isc_local=None):
        n_local = itf_local.shape[0]
        shard = jax.lax.axis_index(axes)
        base = shard * n_local
        itf = itf_local.astype(jnp.float32) \
            if itf_local.dtype != jnp.float32 else itf_local
        scores = vecs @ itf.T            # [B, n_local]
        if isc_local is not None:
            scores = scores * isc_local.reshape(1, -1)
        local_ids = base + jnp.arange(n_local)
        scores = jnp.where((local_ids < n_items)[None, :], scores,
                           -jnp.inf)
        s, i = jax.lax.top_k(scores, k_local)
        gid = jnp.take(local_ids, i)
        # gather the candidate sets along the candidate axis
        s_all = jax.lax.all_gather(s, axes, axis=1,
                                   tiled=True)  # [B, k_local*n_dev]
        g_all = jax.lax.all_gather(gid, axes, axis=1, tiled=True)
        s2, pos = jax.lax.top_k(s_all, s_all.shape[1])
        return jnp.take_along_axis(g_all, pos, axis=1)[:, :k], \
            s2[:, :k]

    spec = rows_spec(mesh)
    if has_scale:
        return jax.jit(jax.shard_map(
            local_rank, mesh=mesh, in_specs=(P(), spec, spec),
            out_specs=(P(), P()), check_vma=False))
    return jax.jit(jax.shard_map(
        local_rank, mesh=mesh, in_specs=(P(), spec),
        out_specs=(P(), P()), check_vma=False))


def _compiled_k(k: int, n_items: int) -> int:
    """Bound jit-cache growth on the serving path: the device kernel always
    runs with k rounded up to a power of two (clamped to the catalog), so
    arbitrary per-query ``num`` values reuse O(log n) compilations; callers
    slice the first ``k`` on the host."""
    k = min(k, n_items)
    p = 1
    while p < k:
        p <<= 1
    return min(p, n_items)


#: host-serving work budget in (batch × factor-matrix elements): under it,
#: serving runs on the HOST (numpy dot + sort, microseconds) instead of
#: paying a per-query device dispatch — SURVEY hard part 3: the reference
#: served from an in-JVM BLAS dot, and a small catalog never justifies
#: the dispatch. Large catalogs — or large
#: coalesced micro-batches over mid-size catalogs — stay on the MXU,
#: where the batched matmul wins.
HOST_SERVE_WORK = 64 * 1024 * 1024


def _host_topk(user_vecs: np.ndarray, item_factors: np.ndarray,
               k: int, n_items: int) -> Tuple[np.ndarray, np.ndarray]:
    """Exact host mirror of the device path: descending score, ties to
    the LOWEST item index (``lax.top_k`` semantics), so a model answers
    identically whichever path serves it."""
    scores = np.asarray(user_vecs) @ np.asarray(item_factors)[:n_items].T
    k = min(k, n_items)
    ids = np.empty((scores.shape[0], k), dtype=np.int64)
    out = np.empty((scores.shape[0], k), dtype=scores.dtype)
    idx_key = np.arange(n_items)
    for b in range(scores.shape[0]):
        order = np.lexsort((idx_key, -scores[b]))[:k]
        ids[b] = order
        out[b] = scores[b, order]
    return ids, out


def _serve_on_host(model: ALSModel, batch: int) -> bool:
    return (isinstance(model.item_factors, np.ndarray)
            and model.item_factors.size * max(batch, 1) <= HOST_SERVE_WORK)


def ensure_device_resident(model: ALSModel,
                           max_batch: int = 1) -> ALSModel:
    """Deploy-time factor placement: models past the host-serving
    budget move into HBM ONCE. A deployed model re-materialized from
    the blob store holds numpy factors, and the serving jits would
    otherwise re-transfer them on EVERY query (~42MB per query at
    ML-20M scale). Small catalogs
    stay host-resident for the host fast path. ``max_batch`` is the
    largest serving batch this surface coalesces (the micro-batcher's
    cap, batch-predict's flush size): a mid-size catalog under the
    batch-1 budget but over the batched one serves on the DEVICE for
    big batches, so it must be device-resident too."""
    import dataclasses

    if _serve_on_host(model, batch=max(max_batch, 1)):
        return model

    def _has_host_leaf(t) -> bool:
        return any(isinstance(leaf, np.ndarray)
                   for leaf in jax.tree_util.tree_leaves(t))

    if _has_host_leaf(model.user_factors) \
            or _has_host_leaf(model.item_factors):
        # device_put maps over pytrees, so quantized tables move their
        # int8/bf16 data + f32 scale leaves in one shot
        return dataclasses.replace(
            model,
            user_factors=jax.device_put(model.user_factors),
            item_factors=jax.device_put(model.item_factors))
    return model


# -- mesh-wide serving placement (ISSUE 6) ----------------------------------

def _pad_rows(arr: np.ndarray, multiple: int) -> np.ndarray:
    """Zero-pad the row axis to a device multiple (even shards)."""
    n = arr.shape[0]
    n_pad = -(-n // multiple) * multiple
    if n_pad == n:
        return arr
    out = np.zeros((n_pad,) + arr.shape[1:], dtype=arr.dtype)
    out[:n] = arr
    return out


def shard_model(model: ALSModel, mesh: Mesh) -> ALSModel:
    """SHARDED serving placement: both factor tables row-sharded over
    every device of the ``(batch, model)`` serving mesh via
    ``NamedSharding`` (ALX's row-sharded factor layout) — the table a
    single HBM cannot hold exists only as per-device shards. Rows are
    zero-padded to a device multiple; ``n_users``/``n_items`` keep the
    real counts so padding can never be served."""
    import dataclasses

    n_dev = mesh.devices.size
    spec = NamedSharding(mesh, rows_spec(mesh))

    def _place(t):
        if isinstance(t, QuantizedFactors):
            # quantized tables shard leaf-wise: int8/bf16 data and the
            # [n, 1] f32 scales land row-sharded together, so a shard
            # can dequantize its rows with no cross-device fetch
            data = np.asarray(jax.device_get(t.data))
            sc = None if t.scale is None \
                else np.asarray(jax.device_get(t.scale))
            return QuantizedFactors(
                jax.device_put(_pad_rows(data, n_dev), spec),
                None if sc is None
                else jax.device_put(_pad_rows(sc, n_dev), spec),
                t.quant)
        arr = np.asarray(t) if isinstance(t, np.ndarray) \
            else jax.device_get(t)
        return jax.device_put(_pad_rows(np.asarray(arr), n_dev), spec)

    return dataclasses.replace(
        model,
        user_factors=_place(model.user_factors),
        item_factors=_place(model.item_factors),
        mesh=mesh)


def replicate_model(model: ALSModel, device) -> ALSModel:
    """REPLICATED serving placement: one full copy of the factor tables
    committed to ``device`` — each replicated-mode lane owns a copy, so
    its dispatches compile and run on its own chip with no cross-device
    sync on the serve path."""
    import dataclasses

    return dataclasses.replace(
        model,
        user_factors=jax.device_put(model.user_factors, device),
        item_factors=jax.device_put(model.item_factors, device),
        mesh=None)


def pin_user_rows(model: ALSModel, user_indices: Sequence[int],
                  capacity: int) -> Tuple[Optional[jax.Array], int]:
    """Hot-entity tier (ISSUE 4): gather the given users' factor rows
    into ONE device-resident ``[capacity, rank]`` table. The table is
    padded to the FIXED capacity so its serving program compiles once
    per process — refreshes that re-rank the hot set reuse the same
    compiled shape instead of paying a post-warm trace per refresh.

    Returns ``(pinned_table, nbytes)``; ``(None, 0)`` for host-served
    models (the host fast path has no gather/transfer to skip).

    Sharded models (``model.mesh`` set) pin a mesh-REPLICATED table:
    the hot rows are fetched once through the GSPMD collective gather
    (the full table never lands on the host) and the [K, rank] result —
    tiny next to the sharded tables — is replicated so every device
    ranks hot users without a per-query cross-shard fetch."""
    if _serve_on_host(model, batch=1) or not len(user_indices):
        return None, 0
    cap = max(int(capacity), 1)
    idx = np.zeros(cap, dtype=np.int64)
    n = min(len(user_indices), cap)
    idx[:n] = np.asarray(list(user_indices)[:n], dtype=np.int64)
    mesh = getattr(model, "mesh", None)
    quant = isinstance(model.user_factors, QuantizedFactors)
    ud, us = _table_leaves(model.user_factors)
    if mesh is not None:
        with _mesh_dispatch_lock:
            # quantized models pin a QUANTIZED table (the hot tier
            # inherits the 4x capacity win); the collective gather
            # moves int8/bf16 rows + f32 scales, never a dequant copy
            rows_dev = _gather_rows_fn(mesh)(ud, idx)
            sc_dev = _gather_rows_fn(mesh)(us, idx) \
                if us is not None else None
            rows_dev.block_until_ready()
        if quant:
            pinned = QuantizedFactors(rows_dev, sc_dev,
                                      model.user_factors.quant)
            return pinned, pinned.nbytes
        return rows_dev, int(rows_dev.nbytes)
    if quant:
        data = np.asarray(jax.device_get(ud))[idx]
        sc = np.asarray(jax.device_get(us))[idx] \
            if us is not None else None
        pinned = QuantizedFactors(
            jax.device_put(data),
            None if sc is None else jax.device_put(sc),
            model.user_factors.quant)
        pinned.data.block_until_ready()
        return pinned, pinned.nbytes
    rows = np.asarray(model.user_factors)[idx]  # one host gather per
    pinned = jax.device_put(rows)               # refresh, not per query
    pinned.block_until_ready()
    return pinned, int(rows.nbytes)


def pin_user_rows_lanes(model: ALSModel, user_indices: Sequence[int],
                        capacity: int, devices: Sequence
                        ) -> Tuple[Optional[tuple], int]:
    """Replicated-mode hot tier: the SAME pinned ``[capacity, rank]``
    table committed once per lane device, so whichever lane serves a
    hot query gathers from its local copy (per-device pinned shards —
    no cross-device traffic on the pinned fast path). Returns
    ``(tables_per_device, total_nbytes)`` or ``(None, 0)``."""
    if _serve_on_host(model, batch=1) or not len(user_indices) \
            or not len(devices):
        return None, 0
    cap = max(int(capacity), 1)
    idx = np.zeros(cap, dtype=np.int64)
    n = min(len(user_indices), cap)
    idx[:n] = np.asarray(list(user_indices)[:n], dtype=np.int64)
    if isinstance(model.user_factors, QuantizedFactors):
        ud, us = _table_leaves(model.user_factors)
        data = np.asarray(jax.device_get(ud))[idx]
        sc = np.asarray(jax.device_get(us))[idx] \
            if us is not None else None
        tables = tuple(
            QuantizedFactors(
                jax.device_put(data, d),
                None if sc is None else jax.device_put(sc, d),
                model.user_factors.quant)
            for d in devices)
        for t in tables:
            t.data.block_until_ready()
        return tables, tables[0].nbytes * len(tables)
    rows = np.asarray(model.user_factors)[idx]
    tables = tuple(jax.device_put(rows, d) for d in devices)
    for t in tables:
        t.block_until_ready()
    return tables, int(rows.nbytes) * len(tables)


def recommend_pinned(model: ALSModel, pinned, slot: int,
                     k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k for one PINNED hot user: the row gather runs against the
    small HBM-resident pinned table instead of the full ``[U, rank]``
    factor matrix (which, for a re-materialized host-resident model,
    would cost a host gather + device transfer on every query).

    ``pinned`` may be a tuple of per-device tables (replicated lanes,
    :func:`pin_user_rows_lanes`) — the copy committed to the SAME
    device as ``model``'s factors is used, so a lane-rotated model
    (``QueryServer._dispatch_predictions``) serves hot queries fully
    lane-local. Sharded models rank the pinned vector through the mesh
    program (each device scores its item shard)."""
    if isinstance(pinned, tuple):
        chosen = pinned[0]
        try:
            devs = _table_leaves(model.item_factors)[0].devices()
            for t in pinned:
                if _table_leaves(t)[0].devices() == devs:
                    chosen = t
                    break
        except Exception:  # noqa: BLE001 — host-resident factors place
            pass           # with any copy; jit decides
        pinned = chosen
    mesh = getattr(model, "mesh", None)
    if mesh is not None:
        k_dev = _compiled_k(k, model.n_items)
        with _mesh_dispatch_lock:
            # ptpu: allow[callback-under-lock] — compiled XLA
            # executables (jitted gather + mesh ranker); they cannot
            # re-enter, and the lock exists to serialize their launch
            pd, ps = _table_leaves(pinned)
            sidx = np.asarray([slot], dtype=np.int64)
            if isinstance(pinned, QuantizedFactors):
                vec = _gather_vecs_fn(mesh, ps is not None)(pd, ps,
                                                            sidx)
            else:
                vec = _gather_rows_fn(mesh)(pd, sidx)  # [1, r]
            ids, scores = _rank_sharded(mesh, vec, model.item_factors,
                                        k_dev, model.n_items)
            k = min(k, model.n_items)
            ids, scores = jax.device_get((ids, scores))
        return ids[0][:k], scores[0][:k]
    k_dev = _compiled_k(k, model.n_items)
    scores, ids = _device_topk(
        pinned, model.item_factors,
        np.asarray([slot], dtype=np.int64), k_dev, model.n_items)
    k = min(k, model.n_items)
    ids, scores = jax.device_get((ids, scores))
    return ids[0][:k], scores[0][:k]


def recommend_products(model: ALSModel, user_index: int, k: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k (item_index, score) for one user — the
    ``ALSModel.recommendProducts`` role (``ALSAlgorithm.scala:95-109``).
    Like the reference, asking for more than the catalog returns the whole
    catalog ranked, never padded rows."""
    if getattr(model, "mesh", None) is not None:
        ids, scores = recommend_batch(
            model, np.asarray([user_index], dtype=np.int64), k)
        return ids[0], scores[0]
    if _serve_on_host(model, batch=1):
        ids, scores = _host_topk(
            np.asarray(model.user_factors)[user_index][None, :],
            model.item_factors, k, model.n_items)
        return ids[0], scores[0]
    k_dev = _compiled_k(k, model.n_items)
    # the index stays uncommitted numpy: jit places it beside the
    # (possibly lane-committed) factors with no device-to-device hop
    scores, ids = _device_topk(
        model.user_factors, model.item_factors,
        np.asarray([user_index], dtype=np.int64), k_dev,
        model.n_items)
    k = min(k, model.n_items)
    ids, scores = jax.device_get((ids, scores))
    return ids[0][:k], scores[0][:k]


#: device top-k rows per dispatch — bounds the [chunk, n_items]
#: score matrix (~230MB at ML-20M catalog) and keeps ONE compiled
#: shape for large eval sweeps
_TOPK_CHUNK = 2048


def _dispatch_topk_chunk(model: ALSModel, user_indices: np.ndarray,
                         k: int):
    """Enqueue ONE top-k device dispatch (batch ≤ ``_TOPK_CHUNK``) and
    return a no-arg resolver that blocks on the device arrays
    (``jax.device_get``) and hands back host ``([B, k] ids, scores)``.

    The dispatch half returns as soon as XLA has the executable
    enqueued — JAX async dispatch — so a staged serving pipeline can
    launch batch k+1 before batch k's results are read back (ISSUE 9).
    The batch axis pads to the pow2 ladder (every distinct [B, r]
    shape is a fresh XLA compile) exactly as the synchronous path
    always did.

    Sharded models launch under ``_mesh_dispatch_lock`` as ever, but
    the readback runs OUTSIDE the lock: fetching an already-enqueued
    result is not a collective launch, so readers never serialize the
    NEXT batch's mesh dispatch behind a device→host transfer."""
    B = len(user_indices)
    kk = min(k, model.n_items)
    k_dev = _compiled_k(k, model.n_items)
    Bp = 1
    while Bp < B:
        Bp *= 2
    idx_dev = np.empty(Bp, dtype=np.int64)
    idx_dev[:B] = user_indices
    idx_dev[B:] = user_indices[0] if B else 0  # pad rows: any valid row
    mesh = getattr(model, "mesh", None)
    if mesh is not None:
        n_dev = mesh.devices.size
        n_pad = _table_leaves(model.item_factors)[0].shape[0]
        if n_pad % n_dev:
            raise ValueError(
                f"item rows {n_pad} not divisible by mesh size "
                f"{n_dev}; pad factors to a device multiple "
                f"(shard_model does)")
        with _mesh_dispatch_lock:
            vecs = _user_vecs(model.user_factors, idx_dev, mesh)
            ids, scores = _rank_sharded(mesh, vecs, model.item_factors,
                                        k_dev, model.n_items)
    else:
        scores, ids = _device_topk(
            model.user_factors, model.item_factors, idx_dev, k_dev,
            model.n_items)

    def resolve() -> Tuple[np.ndarray, np.ndarray]:
        i, s = jax.device_get((ids, scores))
        return i[:B, :kk], s[:B, :kk]

    return resolve


def recommend_batch_async(model: ALSModel, user_indices: np.ndarray,
                          k: int):
    """Dispatch/readback split of :func:`recommend_batch` (ISSUE 9):
    enqueues the device work and returns a no-arg resolver; calling it
    blocks until the results are on the host. Between the two calls
    the device computes while the caller is free to assemble and
    dispatch MORE batches — the continuous-batching serving pipeline's
    contract (docs/serving-pipeline.md).

    Host-served models compute inline (numpy is synchronous; there is
    nothing to overlap) and the resolver just returns the arrays.
    Batches past ``_TOPK_CHUNK`` dispatch every chunk up front — the
    device executes them back to back — and the resolver drains them
    in order."""
    user_indices = np.asarray(user_indices)
    B = len(user_indices)
    kk = min(k, model.n_items)
    if B == 0:
        empty = (np.empty((0, kk), np.int64),
                 np.empty((0, kk), np.float32))
        return lambda: empty
    if getattr(model, "mesh", None) is None \
            and _serve_on_host(model, batch=B):
        host = _host_topk(np.asarray(model.user_factors)[user_indices],
                          model.item_factors, k, model.n_items)
        return lambda: host
    resolvers = [
        _dispatch_topk_chunk(model, user_indices[s:s + _TOPK_CHUNK], k)
        for s in range(0, B, _TOPK_CHUNK)]
    if len(resolvers) == 1:
        return resolvers[0]

    def resolve() -> Tuple[np.ndarray, np.ndarray]:
        parts = [r() for r in resolvers]
        return (np.concatenate([p[0] for p in parts], axis=0),
                np.concatenate([p[1] for p in parts], axis=0))

    return resolve


def recommend_batch(model: ALSModel, user_indices: np.ndarray, k: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Micro-batched top-k for many users (one device dispatch, or the
    host path for small models + small batches). Sharded models
    (``model.mesh``) rank over the mesh: cross-shard user gather +
    per-device item-shard top-k + candidate merge, with the batch axis
    padded to the same pow2 ladder as the single-device path so the
    micro-batcher's arbitrary batch sizes reuse O(log) compilations.

    Realized as :func:`recommend_batch_async` + immediate readback so
    the synchronous and pipelined paths can never diverge."""
    return recommend_batch_async(model, user_indices, k)()


def _host_row_f32(t, i: int) -> np.ndarray:
    """One factor row as host f32, dequantizing if needed."""
    data, scale = _table_leaves(t)
    row = np.asarray(jax.device_get(data[i])).astype(np.float32)
    if scale is not None:
        row = row * float(np.asarray(jax.device_get(scale[i]))[0])
    return row


def predict_rating(model: ALSModel, user_index: int, item_index: int) -> float:
    u = _host_row_f32(model.user_factors, user_index)
    v = _host_row_f32(model.item_factors, item_index)
    return float(u @ v)


# -- streaming fold-in (ISSUE 10) --------------------------------------------
#
# The incremental-training primitives the StreamTrainer
# (predictionio_tpu/streaming/) folds fresh events in with: per-entity
# regularized least-squares solves against the FIXED opposite factor
# table — one half-iteration of ALS restricted to the affected rows.
# Because each row is re-solved from its FULL history, folding the same
# events in twice lands on the same row: replay after a crash is
# idempotent, which is what makes the cursor's at-least-once delivery
# effectively exactly-once (docs/streaming.md).

def dedupe_pairs(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Collapse repeated ``(row, col)`` pairs to the LAST value
    (last-write-wins, in input order). A burst of identical events must
    not multiply a pair's weight in the normal equations: under
    implicit ALS every duplicate adds another ``alpha·r`` of confidence
    for the SAME observation, and under explicit ALS the duplicated
    entry counts as extra evidence — both skew the fold-in relative to
    the batch trainer, whose input is one rating per (user, item)
    (regression-tested by tests/test_streaming.py)."""
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    if len(rows) == 0:
        return rows, cols, vals
    # np.unique keeps the FIRST occurrence per key; index from the back
    # so "first of reversed" is the last write
    key = np.stack([rows[::-1], cols[::-1]], axis=1)
    _, first_of_rev = np.unique(key, axis=0, return_index=True)
    keep = np.sort(len(rows) - 1 - first_of_rev)
    return rows[keep], cols[keep], vals[keep]


def fixed_gramian(fixed, params: "ALSParams"):
    """The implicit-path baseline Gramian FᵀF of the fixed side, for
    callers that amortize it across fold-in micro-batches (it depends
    only on the fixed table, not on which rows are being re-solved).
    Explicit models need none — returns None."""
    if not params.implicit_prefs:
        return None
    # a quantized serving table dequantizes once here (elementwise —
    # sharding preserved): fold-in math stays f32 against the same
    # values serving scores with
    arr = jnp.asarray(dequantize_table(fixed))
    if _is_row_sharded(arr):
        with _mesh_dispatch_lock:  # the reduction launches collectives
            return _fixed_gramian(arr)
    return _fixed_gramian(arr)


def _pow2_ceil(n: int, lo: int = 1) -> int:
    p = lo
    while p < n:
        p <<= 1
    return p


def fold_in_rows(fixed, indices: np.ndarray, values: np.ndarray,
                 counts: np.ndarray, params: "ALSParams",
                 G=None) -> np.ndarray:
    """Batched per-row fold-in: solve ``[B]`` rows' normal equations
    against the fixed opposite factor table — the streaming increment's
    device path. Routes through :func:`_update_block` (and therefore
    :func:`_lhs_fn`), so it shares the normal-equation build, the
    bf16 gather shadow and the implicit/explicit weighting with the
    batch trainer — the two solvers can never drift apart.

    ``indices``/``values`` are ``[B, L]`` histories (padding slots
    carry index 0 / value 0 and are masked by ``counts``). The batch
    and history axes pad to the pow2 ladder so arbitrary micro-batch
    shapes reuse O(log²) compilations. ``G`` (optional) is a
    precomputed fixed-side Gramian (:func:`fixed_gramian`); implicit
    callers that fold many micro-batches against one model should pass
    it rather than paying the O(n·r²) reduction per batch.

    Returns host ``[B, rank]`` f32 rows.
    """
    indices = np.asarray(indices, dtype=np.int32)
    values = np.asarray(values, dtype=np.float32)
    counts = np.asarray(counts, dtype=np.int32)
    B, L = indices.shape
    if B == 0:
        return np.zeros((0, fixed.shape[-1]), np.float32)
    Bp = _pow2_ceil(B)
    Lp = _pow2_ceil(max(L, 1), lo=8)
    idx = np.zeros((1, Bp, Lp), dtype=np.int32)
    val = np.zeros((1, Bp, Lp), dtype=np.float32)
    cnt = np.zeros((1, Bp), dtype=np.int32)
    idx[0, :B, :L] = indices
    val[0, :B, :L] = values
    cnt[0, :B] = counts
    implicit = params.implicit_prefs
    bf16 = params.matmul_dtype == "bfloat16"
    # quantized serving tables (ISSUE 13) dequantize for the solve —
    # the fold-in's normal equations stay f32 against the values the
    # table actually serves
    table = jnp.asarray(dequantize_table(fixed))

    def _solve():
        nonlocal G
        if implicit and G is None:
            G = _fixed_gramian(table)
        if not implicit:
            # static-arg shape filler, exactly like _update_side_split
            G = jnp.zeros((table.shape[-1],) * 2, jnp.float32)
        gsrc = table.astype(jnp.bfloat16) \
            if params.gather_dtype == "bfloat16" else table
        # debug_numerics routes the solve through checkify (NaN/Inf
        # attributed HERE, before a hot-swap can poison the serving
        # table); pass-through one bool check when off
        new = _numerics.checked_call(
            "fold_in_rows", _update_block, gsrc, G, idx, val, cnt,
            params.reg, params.alpha, implicit,
            params.scale_reg_by_count, bf16=bf16, mesh=None)
        return np.asarray(jax.device_get(new[0][:B]), dtype=np.float32)

    if _is_row_sharded(table):
        # row-sharded serving table (ISSUE 6): GSPMD resolves the
        # gathers with collectives — launches must not interleave with
        # a concurrent serving dispatch's, exactly like recommend_*
        with _mesh_dispatch_lock:
            return _solve()
    return _solve()


def _scatter_rows(table: jax.Array, row_idx: np.ndarray,
                  rows: np.ndarray) -> jax.Array:
    """Functional device row update (NO donation: the previous table
    may still be serving through the old binding until the swap
    lands). The index axis pads to the pow2 ladder — duplicates of
    slot 0 re-write the same value, so padding is inert."""
    B = len(row_idx)
    Bp = _pow2_ceil(max(B, 1))
    idx = np.empty(Bp, dtype=np.int64)
    idx[:B] = row_idx
    idx[B:] = row_idx[0] if B else 0
    # rows keep their own dtype (int8/bf16 for re-quantized hot-swap
    # rows; f32 otherwise) — the jitted set casts to the table's
    vals = np.empty((Bp, rows.shape[-1]), dtype=rows.dtype)
    vals[:B] = rows
    vals[B:] = rows[0] if B else 0
    return _scatter_rows_fn(jnp.asarray(table), idx, vals)


@jax.jit
def _scatter_rows_fn(table: jax.Array, idx: jax.Array,
                     rows: jax.Array) -> jax.Array:
    return table.at[idx].set(rows.astype(table.dtype))


def apply_row_updates(model: ALSModel, side: str, row_idx: np.ndarray,
                      rows: np.ndarray) -> ALSModel:
    """A NEW model with ``side``'s factor rows at ``row_idx`` replaced
    by ``rows`` — the delta the streaming trainer hot-swaps into the
    serving binding. Purely functional: the input model (possibly still
    bound and serving) is never mutated, so a reader holding the old
    binding keeps a consistent table.

    Host-resident tables copy-and-write (numpy); device tables scatter
    through a compiled ``at[].set`` (no donation — see above); row-
    sharded tables run the same scatter under ``_mesh_dispatch_lock``
    (GSPMD keeps the output sharding) so a concurrent serving dispatch
    can't interleave collective launches."""
    import dataclasses

    if side not in ("user", "item"):
        raise ValueError(f"side must be 'user' or 'item', got {side!r}")
    name = "user_factors" if side == "user" else "item_factors"
    table = getattr(model, name)
    row_idx = np.asarray(row_idx, dtype=np.int64)
    rows = np.asarray(rows, dtype=np.float32)
    if len(row_idx) == 0:
        return model
    if isinstance(table, QuantizedFactors):
        # streaming hot-swap into a quantized serving table (ISSUE 13):
        # the freshly solved f32 rows RE-QUANTIZE on the way in — data
        # and per-row scales swap together, so a swapped row serves
        # with its own scale, never a stale one
        qd, qs = _quantize_rows(rows, table.quant)

        def _swap_leaves(data_new, scale_new):
            return dataclasses.replace(model, **{name: QuantizedFactors(
                data_new, scale_new, table.quant)})

        if isinstance(table.data, np.ndarray):
            data = table.data.copy()
            data[row_idx] = qd
            scale = None
            if table.scale is not None:
                scale = table.scale.copy()
                scale[row_idx] = qs
            return _swap_leaves(data, scale)
        if _is_row_sharded(table.data):
            with _mesh_dispatch_lock:
                data = _scatter_rows(table.data, row_idx, qd)
                data.block_until_ready()
                scale = None
                if table.scale is not None:
                    scale = _scatter_rows(table.scale, row_idx, qs)
                    scale.block_until_ready()
            return _swap_leaves(data, scale)
        data = _scatter_rows(table.data, row_idx, qd)
        scale = _scatter_rows(table.scale, row_idx, qs) \
            if table.scale is not None else None
        return _swap_leaves(data, scale)
    if isinstance(table, np.ndarray):
        new = table.copy()
        new[row_idx] = rows
    elif _is_row_sharded(table):
        with _mesh_dispatch_lock:
            new = _scatter_rows(table, row_idx, rows)
            new.block_until_ready()
    else:
        new = _scatter_rows(table, row_idx, rows)
    return dataclasses.replace(model, **{name: new})


#: cold-start capacity growth floor: when a side's table has no free
#: padding rows left, it grows by at least this many zero rows at once
#: so per-entity appends don't re-allocate (and re-place) the table on
#: every single new user/item
COLD_START_GROW_MIN = 64


def extend_factor_rows(model: ALSModel, side: str, new_keys: Sequence[str],
                       rows: np.ndarray) -> ALSModel:
    """Cold-start row insertion (ISSUE 10): register ``new_keys`` as
    fresh entities on ``side`` with the given factor rows. Training
    pads factor tables past ``n_users``/``n_items`` for even sharding —
    those zero padding rows are CLAIMED first (no reallocation, no new
    compiled serving shapes beyond the n_items bump); only when the
    table is full does it grow, by pow2-rounded chunks
    (:data:`COLD_START_GROW_MIN`), with the new capacity again zero-
    padded. Returns a new model: extended id map, bumped real count,
    rows written via :func:`apply_row_updates`."""
    import dataclasses

    from ..data.bimap import BiMap

    if side not in ("user", "item"):
        raise ValueError(f"side must be 'user' or 'item', got {side!r}")
    new_keys = list(new_keys)
    if not new_keys:
        return model
    name = "user_factors" if side == "user" else "item_factors"
    ids_name = "user_ids" if side == "user" else "item_ids"
    count_name = "n_users" if side == "user" else "n_items"
    table = getattr(model, name)
    ids = getattr(model, ids_name)
    n_real = getattr(model, count_name)
    rows = np.asarray(rows, dtype=np.float32)
    if rows.shape[0] != len(new_keys):
        raise ValueError(f"{len(new_keys)} keys but {rows.shape[0]} rows")
    for k in new_keys:
        if ids is not None and k in ids:
            raise ValueError(f"{side} {k!r} already indexed; fold in "
                             f"through apply_row_updates instead")
    n_after = n_real + len(new_keys)
    capacity = int(table.shape[0])
    if n_after > capacity:
        grow = _pow2_ceil(max(n_after - capacity, COLD_START_GROW_MIN))
        mesh = getattr(model, "mesh", None)

        def _grow_arr(arr, grow_n, fill):
            if isinstance(arr, np.ndarray):
                extra = np.full((grow_n,) + arr.shape[1:], fill,
                                arr.dtype)
                return np.concatenate([arr, extra], axis=0)
            if mesh is not None and _is_row_sharded(arr):
                # sharded growth: pull the shards together once,
                # extend to a device multiple, re-place row-sharded
                # (the same placement shard_model derives)
                host = jax.device_get(arr)
                host = np.concatenate(
                    [host, np.full((grow_n,) + host.shape[1:], fill,
                                   host.dtype)], axis=0)
                host = _pad_rows(host, mesh.devices.size)
                return jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
            pad = jnp.full((grow_n,) + arr.shape[1:], fill, arr.dtype)
            return jnp.concatenate([jnp.asarray(arr), pad], axis=0)

        if isinstance(table, QuantizedFactors):
            # claimed rows are re-quantized by the apply below; the
            # fresh capacity carries zero rows with scale 1 (inert)
            table = QuantizedFactors(
                _grow_arr(table.data, grow, 0),
                None if table.scale is None
                else _grow_arr(table.scale, grow, 1.0),
                table.quant)
        else:
            table = _grow_arr(table, grow, 0)
    fwd = dict(ids.items()) if ids is not None else {}
    for i, k in enumerate(new_keys):
        fwd[k] = n_real + i
    model = dataclasses.replace(
        model, **{name: table, ids_name: BiMap(fwd), count_name: n_after})
    return apply_row_updates(
        model, side, np.arange(n_real, n_after, dtype=np.int64), rows)
