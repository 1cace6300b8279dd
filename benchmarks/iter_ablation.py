"""Ablation profile of the FULL ALS iteration at bench scale.

iter_scaling (round 4) split the iteration into a rank-independent
~0.4s component and an r² math term — but per-stage microbenches
show every stage at multi-TF/s on small batches, so the
bound hides at FULL problem scale. This probe times the real iteration
body (both halves, real bucketed layout, 20M entries) with stages
successively disabled, inside a DCE-proof fori_loop. The difference between adjacent stages is that stage's true
full-scale cost, dispatch overhead excluded.

Stages (cumulative): gather → gram → +rhs → +solve → full (+scatter).
Plus isolated: a standalone solve on a random SPD batch.

Usage: python benchmarks/iter_ablation.py
Env:   ABL_NNZ=20000000 ABL_RANK=64 ABL_REPS=2 ABL_INNER=3
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    nnz = int(os.environ.get("ABL_NNZ", "20000000"))
    rank = int(os.environ.get("ABL_RANK", "64"))
    reps = int(os.environ.get("ABL_REPS", "2"))
    K = int(os.environ.get("ABL_INNER", "3"))

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.als import (
        ALSParams,
        RatingsCOO,
        _auto_block_rows,
        pack_ratings,
    )
    from predictionio_tpu.ops.gram import gram_weighted
    from predictionio_tpu.ops.ragged import BucketedHistories
    from predictionio_tpu.ops.solve import gramian, solve_spd_batch

    n_users = max(int(138_000 * nnz / 20_000_000), 64)
    n_items = max(int(27_000 * nnz / 20_000_000), 64)
    items = (np.random.default_rng(1).zipf(1.3, size=nnz)
             % n_items).astype(np.int32)
    users = np.random.default_rng(0).integers(
        0, n_users, nnz).astype(np.int32)
    ratings = RatingsCOO(users, items, np.ones(nnz, np.float32),
                         n_users, n_items)
    params = ALSParams(rank=rank, num_iterations=1,
                       implicit_prefs=True, alpha=40.0, reg=0.01,
                       seed=3)
    packed = pack_ratings(ratings, params)
    kinds = {s: ("bucket" if isinstance(
        getattr(packed, f"{s}_h"), BucketedHistories) else "pad")
        for s in ("user", "item")}
    print(json.dumps({"layout": kinds, "nnz": nnz, "rank": rank}),
          flush=True)

    uh = packed.blocked("user", 1, None)
    ih = packed.blocked("item", 1, None)
    rng = np.random.default_rng(2)
    key = jax.random.key(3)
    ku, ki = jax.random.split(key)

    def rows_padded(lay):
        if "buckets" in lay:
            return lay["n_rows_padded"]
        d, n_per, _ = lay["idx"].shape
        return d * n_per

    nu, ni = rows_padded(uh), rows_padded(ih)
    U = jax.random.normal(ku, (nu, rank), jnp.float32) * 0.01
    V = jax.random.normal(ki, (ni, rank), jnp.float32) * 0.01

    def buckets_of(lay, h):
        if "buckets" in lay:
            return list(lay["buckets"]), True
        d, n_per, L = lay["idx"].shape
        block = _auto_block_rows(n_per, L, rank)
        return [{"idx": lay["idx"], "val": lay["val"],
                 "cnt": lay["cnt"], "rid": None,
                 "block": block}], False

    def half(fixed, out0, lay, stage):
        """The real half-iteration body with later stages disabled.
        Returns (out, acc); acc folds every produced value so nothing
        is DCE'd."""
        G = gramian(fixed)
        acc = jnp.float32(0.0)
        out = out0
        bks, is_bucket = buckets_of(lay, None)
        for b in bks:
            d, n_per, L = b["idx"].shape
            block = b.get("block") or _auto_block_rows(n_per, L, rank)
            parts = []
            for s in range(0, n_per, block):
                e = min(s + block, n_per)
                idx = b["idx"][:, s:e]
                val = b["val"][:, s:e]
                cnt = b["cnt"][:, s:e]
                Lb = idx.shape[-1]
                valid = (jnp.arange(Lb)[None, None, :]
                         < cnt[:, :, None]).astype(jnp.float32)
                F = fixed[idx]
                if stage == "gather":
                    acc += jnp.sum(F)
                    continue
                c1 = params.alpha * val * valid
                A = G[None, None] + gram_weighted(F, c1)
                if stage == "gram":
                    acc += jnp.sum(A)
                    continue
                bv = jnp.einsum("dnlr,dnl->dnr", F, (c1 + 1.0) * valid)
                if stage == "gramrhs":
                    acc += jnp.sum(A) + jnp.sum(bv)
                    continue
                A = A + params.reg * jnp.eye(rank, dtype=A.dtype)
                new = solve_spd_batch(A, bv)
                if stage == "solve":
                    acc += jnp.sum(new)
                    continue
                parts.append(new)
            if stage in ("gather", "gram", "gramrhs", "solve"):
                continue
            new = parts[0] if len(parts) == 1 \
                else jnp.concatenate(parts, axis=1)
            if is_bucket:
                out = out.at[b["rid"]].set(
                    new.reshape(d * n_per, rank), mode="drop",
                    unique_indices=True)
            else:
                out = new.reshape(d * n_per, rank)
        return out, acc

    def iteration(U0, V0, stage):
        u_out, acc_u = half(V0, jnp.zeros_like(U0), uh, stage)
        fixed_next = u_out if stage == "full" else V0
        v_out, acc_v = half(
            (U0 if stage != "full" else u_out),
            jnp.zeros_like(V0), ih, stage)
        return (jnp.sum(u_out) + jnp.sum(v_out) + acc_u + acc_v
                if stage == "full"
                else acc_u + acc_v + jnp.sum(fixed_next[0, 0]))

    def sync(x):
        np.asarray(jax.device_get(jnp.ravel(x)[:1]))

    # empty-dispatch baseline
    _zero = jax.jit(lambda x: x + 1.0)
    z = jnp.float32(0.0)
    _zero(z)
    sync(_zero(z))
    t_zero = float("inf")
    for _ in range(max(reps, 3)):
        t0 = time.monotonic()
        sync(_zero(z))
        t_zero = min(t_zero, time.monotonic() - t0)
    print(json.dumps({"stage": "dispatch_baseline",
                      "ms": round(t_zero * 1e3, 1)}), flush=True)

    def timed_stage(stage):
        def looped(U0, V0):
            def body(_i, carry):
                return iteration(U0 + carry * 1e-30,
                                 V0 + carry * 1e-30, stage)
            return jax.lax.fori_loop(0, K, body, jnp.float32(0.0))

        lfn = jax.jit(looped)
        try:
            lfn(U, V)
            sync(lfn(U, V))
        except Exception as e:  # noqa: BLE001 — report, keep going
            print(json.dumps({"stage": stage,
                              "error": str(e)[:200]}), flush=True)
            return None
        best = float("inf")
        for _ in range(reps):
            t0 = time.monotonic()
            sync(lfn(U, V))
            best = min(best, time.monotonic() - t0)
        dt = (best - t_zero) / K
        print(json.dumps({"stage": stage,
                          "s_per_iter": round(dt, 4)}), flush=True)
        return dt

    known = ("gather", "gram", "gramrhs", "solve", "full")
    stages = os.environ.get("ABL_STAGES", ",".join(known)).split(",")
    for stage in stages:
        # an unknown name would trace the full body but fold NOTHING
        # into the carry — XLA then eliminates all the work and the
        # "measurement" is the dispatch baseline wearing a stage label
        if stage not in known:
            print(json.dumps({"stage": stage,
                              "error": f"unknown stage (known: {known})"
                              }), flush=True)
            continue
        timed_stage(stage)

    # isolated: solve on a random SPD batch the size of both sides
    B = nu + ni
    M = jnp.asarray(rng.standard_normal((B, rank, rank)),
                    jnp.float32) * 0.1
    eye = jnp.eye(rank, dtype=jnp.float32)

    def solve_only(Ms):
        A = jnp.einsum("brs,bts->brt", Ms, Ms) + eye[None]
        return solve_spd_batch(A, Ms[:, :, 0])

    def looped_solve(Ms):
        def body(_i, carry):
            return jnp.sum(solve_only(Ms + carry * 1e-30)).astype(
                jnp.float32)
        return jax.lax.fori_loop(0, K, body, jnp.float32(0.0))

    lfn = jax.jit(looped_solve)
    lfn(M)
    sync(lfn(M))
    best = float("inf")
    for _ in range(reps):
        t0 = time.monotonic()
        sync(lfn(M))
        best = min(best, time.monotonic() - t0)
    print(json.dumps({"stage": "solve_isolated", "batch": int(B),
                      "s": round((best - t_zero) / K, 4)}), flush=True)


if __name__ == "__main__":
    main()
