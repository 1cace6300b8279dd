"""Stage profile of the ALS half-step on the attached device.

Answers the MFU question with measurements instead of guesses
(VERDICT r2 weak #2: the whole-iteration number alone cannot say
whether the bound is the gather, the gram einsum, the solves, or the
scatters). For the bench shape (and a rank sweep) it times, each
hard-synced via a device→host transfer:

- ``gather``: F = fixed[indices] materialization alone
- ``gram_einsum``: baseline batched weighted gram from pre-gathered F
- ``gram_pair``: the 2-rows-per-MXU-tile packing (ops/gram.py)
- ``gram_fused``/``gram_pair_fused``: gather + gram in ONE jit (what
  the half-step actually runs — XLA may fuse the gather)
- ``solve``: the Pallas lane-batched Cholesky on [B, r, r]
- bf16 variants of the gram stages

Prints one JSON line per (rank, stage).

Usage: python benchmarks/gram_profile.py [B] [L]
Env:   GRAM_RANKS="32,64,128", GRAM_REPS=3
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
    pos = [a for a in sys.argv[1:] if not a.startswith("-")]
    B = int(pos[0]) if len(pos) > 0 else 4096
    L = int(pos[1]) if len(pos) > 1 else 256
    ranks = [int(r) for r in
             os.environ.get("GRAM_RANKS", "32,64,128").split(",")]
    reps = int(os.environ.get("GRAM_REPS", "3"))
    n_fixed = 140_000

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.utils.platform import force_cpu_if_requested
    force_cpu_if_requested()

    from predictionio_tpu.ops.gram import gram_pairs, gram_weighted
    from predictionio_tpu.ops.solve import solve_spd_batch

    dev = jax.devices()[0].device_kind
    rng = np.random.default_rng(0)
    idx_h = rng.integers(0, n_fixed, (1, B, L)).astype(np.int32)
    w_h = rng.random((1, B, L)).astype(np.float32)

    def sync(x):
        np.asarray(jax.device_get(jnp.ravel(x)[:1]))

    # Per-dispatch overhead is of the same order as these small ops
    # (0.9 ms p50 for an empty dispatch on the v5e, CHANGES.md PR 21),
    # so a single-op timing would measure the dispatch.
    # Each stage therefore runs K times inside ONE jitted fori_loop —
    # the carry feeds the next rep's input so nothing is DCE'd or
    # hoisted — and per-rep time is (T_loop - T_zero)/K with T_zero a
    # measured empty-dispatch baseline.
    K = int(os.environ.get("GRAM_INNER_REPS", "16"))

    def timeit(fn, *args):
        # every stage's first arg is a float array; the carry feeds it
        # so reps can't be hoisted, and the carry is a FULL-output sum
        # so XLA can't slice-sink/DCE the op being timed
        assert args[0].dtype.kind == "f", "first arg must be float"

        def looped(*a):
            def body(_i, carry):
                out = fn(a[0] + carry * 1e-30, *a[1:])
                return jax.tree_util.tree_reduce(
                    lambda acc, leaf: acc + jnp.sum(leaf).astype(
                        jnp.float32),
                    out, jnp.float32(0.0))

            return jax.lax.fori_loop(0, K, body, jnp.float32(0.0))

        lfn = jax.jit(looped)
        lfn(*args)  # compile + warm
        sync(lfn(*args))
        best = float("inf")
        for _ in range(reps):
            t0 = time.monotonic()
            out = lfn(*args)
            sync(out)
            best = min(best, time.monotonic() - t0)
        dt = (best - t_zero) / K
        if dt <= t_zero * 0.5 / K:
            return None  # below measurement resolution — don't report
        return dt

    # empty-dispatch baseline: same jit/sync plumbing, ~no compute
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
                      "ms": round(t_zero * 1e3, 3)}), flush=True)

    def emit(stage, r, dt, flops=None, **extra):
        """One output contract for every stage: ms/useful_tflops are
        null with below_resolution=true when dt is None."""
        print(json.dumps({
            "stage": stage, "rank": r, "B": B, "L": L,
            "ms": (round(dt * 1e3, 3) if dt else None),
            **({"below_resolution": True} if dt is None else {}),
            "useful_tflops": (round(flops / dt / 1e12, 3)
                              if dt and flops else None),
            "device": dev, **extra}), flush=True)

    for r in ranks:
        fixed = jnp.asarray(
            rng.standard_normal((n_fixed, r)).astype(np.float32))
        idx = jnp.asarray(idx_h)
        w = jnp.asarray(w_h)

        gather = jax.jit(lambda f, i: f[i])
        F = gather(fixed, idx)
        F.block_until_ready()

        stages = {
            "gather": (gather, fixed, idx),
            "gram_einsum": (jax.jit(gram_weighted), F, w),
            "gram_pair": (jax.jit(gram_pairs), F, w),
            "gram_einsum_bf16": (
                jax.jit(lambda F, w: gram_weighted(F, w, bf16=True)),
                F, w),
            "gram_pair_bf16": (
                jax.jit(lambda F, w: gram_pairs(F, w, bf16=True)),
                F, w),
            "gram_fused": (
                jax.jit(lambda f, i, w: gram_weighted(f[i], w)),
                fixed, idx, w),
            "gram_pair_fused": (
                jax.jit(lambda f, i, w: gram_pairs(f[i], w)),
                fixed, idx, w),
            "gram_fused_bf16": (
                jax.jit(lambda f, i, w: gram_weighted(f[i], w,
                                                      bf16=True)),
                fixed, idx, w),
            "gram_pair_fused_bf16": (
                jax.jit(lambda f, i, w: gram_pairs(f[i], w, bf16=True)),
                fixed, idx, w),
        }
        # useful FLOPs of the weighted gram (the pair layout does 2x the
        # multiplies; report against USEFUL work so variants compare)
        gram_flops = 2.0 * B * L * r * r
        stage_ms: dict[str, float] = {}
        for name, (fn, *args) in stages.items():
            dt = timeit(fn, *args)
            emit(name, r, dt,
                 flops=(gram_flops if "gram" in name else None))
            if dt is not None:
                stage_ms[name] = dt

        # fused VMEM-table kernel: the user-half-step scenario (gather
        # from the ITEM table, which fits VMEM at MovieLens shapes)
        from predictionio_tpu.ops.gram import (
            gram_table_pallas,
            gram_table_supported,
        )
        n_small = 27_000
        skip = None
        if not gram_table_supported():
            skip = "lowering unsupported on this backend"
        elif n_small * r * 4 > 12 * 2**20:
            skip = "table exceeds the VMEM budget at this rank"
        if skip is None:
            tab_s = jnp.asarray(rng.standard_normal(
                (n_small, r)).astype(np.float32))
            idx_s = jnp.asarray(
                rng.integers(0, n_small, (B, L)).astype(np.int32))
            w2 = jnp.asarray(w_h[0])
            try:
                # the support probe runs a tiny shape; a size-dependent
                # Mosaic failure here must not kill the remaining stages
                dt = timeit(jax.jit(gram_table_pallas), tab_s, idx_s,
                            w2, w2)
            except Exception as e:  # noqa: BLE001 — report, keep going
                skip = f"compile/run failed at real shape: {e}"[:300]
            else:
                emit("gram_table_pallas", r, dt, flops=gram_flops)
        if skip is not None:
            print(json.dumps({
                "stage": "gram_table_pallas", "rank": r,
                "skipped": skip, "device": dev}), flush=True)

        # the HBM-streaming fused gather+gram kernel (ISSUE 7,
        # ops/fused_gram.py): the table STAYS in HBM, rows DMA into
        # double-buffered VMEM tiles — the gram_mode="fused"
        # realization, raced here at the same shapes so --record can
        # persist a three-way winner
        from predictionio_tpu.ops.fused_gram import (
            fused_gram,
            fused_gram_refusal,
        )

        for kname, tab in (
                ("gram_kernel_fused", fixed),
                ("gram_kernel_fused_bf16", fixed.astype(jnp.bfloat16))):
            # compile at the shapes about to run; a refusal is reported
            # with the compiler's message, never timed as something else
            why = fused_gram_refusal(r, tab.dtype, L)
            if why is not None:
                print(json.dumps({
                    "stage": kname, "rank": r,
                    "skipped": why[:300], "device": dev}), flush=True)
                continue
            dt = timeit(jax.jit(fused_gram), tab, idx, w, w)
            emit(kname, r, dt, flops=gram_flops)
            if dt is not None:
                stage_ms[kname] = dt

        # --record: persist the fused-variant winners (the half-step's
        # actual realization: gather+gram in one jit) into the
        # shape-keyed autotune table consulted by gram_mode="auto"
        if "--record" in sys.argv:
            from predictionio_tpu.ops.gram_autotune import record

            for bf16, ein, pair, kern in (
                    (False, "gram_fused", "gram_pair_fused",
                     "gram_kernel_fused"),
                    (True, "gram_fused_bf16", "gram_pair_fused_bf16",
                     "gram_kernel_fused_bf16")):
                if ein in stage_ms and pair in stage_ms:
                    cands = {"einsum": stage_ms[ein],
                             "pair": stage_ms[pair]}
                    if kern in stage_ms:
                        # the Pallas kernel joins the race wherever it
                        # lowered; its absence (no TPU, Mosaic too old)
                        # keeps the two-way einsum/pair contest
                        cands["fused"] = stage_ms[kern]
                    win = min(cands, key=cands.get)
                    measured = {
                        "source": "gram_profile",
                        "einsum_ms": round(stage_ms[ein] * 1e3, 3),
                        "pair_ms": round(stage_ms[pair] * 1e3, 3),
                    }
                    if kern in stage_ms:
                        measured["fused_ms"] = round(
                            stage_ms[kern] * 1e3, 3)
                    persisted = record(r, win, bf16=bf16,
                                       device_kind=dev,
                                       measured=measured)
                    print(json.dumps({
                        "recorded": win if persisted else None,
                        "persisted": persisted, "rank": r,
                        "bf16": bf16, "device": dev}), flush=True)

        A_h = rng.standard_normal((B, r, r)).astype(np.float32)
        A = jnp.asarray(A_h @ A_h.transpose(0, 2, 1)
                        + 10.0 * np.eye(r, dtype=np.float32))
        b = jnp.asarray(rng.standard_normal((B, r)).astype(np.float32))
        dt = timeit(jax.jit(solve_spd_batch), A, b)
        emit("solve_spd", r, dt)


if __name__ == "__main__":
    main()
