"""Benchmark: implicit-ALS training throughput on the flagship workload.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu",
"cpu_baseline_measured", "dropped_entries", ...}.

Honesty model (BASELINE.md "bench accounting"):

- The workload is a synthetic MovieLens-20M-shaped problem (138k users ×
  27k items, 20M implicit ratings, zipf(1.3) item skew, rank 64).
- ``history_mode="split"`` trains on **every** rating regardless of skew
  (``dropped_entries`` is asserted 0) — the same contract as MLlib ALS,
  which uses every rating (reference ``ALSAlgorithm.scala:75-85``).
- ``vs_baseline`` divides by a CPU baseline **measured in this same
  process on this same host**: a numpy/BLAS implementation of the
  identical Hu-Koren-Volinsky + ALS-WR math (CSR per-row gemms + batched
  LAPACK solves — structurally what MLlib does inside each Spark task),
  run on a 1/10-scale slice and reported per-rating. The reference
  publishes no numbers of its own (BASELINE.md: "none found").
- ``mfu`` is achieved FLOP/s over the chip's peak, where achieved FLOP/s
  uses the padded-work FLOP model (`als_flops_per_iter`) — the work the
  device actually executes — and peak is the device's headline bf16
  matmul rate (conservative for this f32 run; see table below).
"""

import json
import os
import sys
import time

import numpy as np

#: Headline peak matmul FLOP/s by TPU generation (bf16; public spec
#: sheets). MFU is reported against this even though the bench runs f32 —
#: a conservative (lower) MFU. Unknown devices → mfu null.
PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def device_peak_flops() -> float | None:
    """Peak for ONE device — the bench trains meshless on a single chip
    (the driver exposes one real TPU), so multi-device peaks would
    understate MFU."""
    import jax

    kind = jax.devices()[0].device_kind
    for name, peak in PEAK_FLOPS.items():
        if kind.startswith(name):
            return peak
    return None


def cpu_als_baseline(n_users: int, n_items: int, nnz: int, rank: int,
                     alpha: float, reg: float, seed: int = 7) -> float:
    """Measured same-host CPU throughput (ratings/s/iter) of the identical
    implicit-ALS math in numpy: per-row CSR gemms for the normal-equation
    blocks + one batched LAPACK solve per side. This is the MLlib-ALS
    structural equivalent (per-user solves inside tasks) on this machine's
    CPU/BLAS; timing excludes CSR packing, mirroring the TPU bench which
    times iterations with ``packed=`` reuse."""
    rng = np.random.default_rng(seed)
    items = (np.random.default_rng(seed + 1).zipf(1.3, size=nnz)
             % n_items).astype(np.int32)
    users = rng.integers(0, n_users, nnz).astype(np.int32)
    vals = np.ones(nnz, dtype=np.float32)

    def csr(rows, cols, v, n_rows):
        order = np.argsort(rows, kind="stable")
        r, c, w = rows[order], cols[order], v[order]
        counts = np.bincount(r, minlength=n_rows)
        indptr = np.zeros(n_rows + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        return indptr, c, w

    u_indptr, u_cols, u_vals = csr(users, items, vals, n_users)
    i_indptr, i_cols, i_vals = csr(items, users, vals, n_items)

    U = (rng.standard_normal((n_users, rank)).astype(np.float32)
         / np.sqrt(rank))
    V = (rng.standard_normal((n_items, rank)).astype(np.float32)
         / np.sqrt(rank))

    def half_step(fixed, indptr, cols, w, n_rows):
        G = fixed.T @ fixed
        A = np.empty((n_rows, rank, rank), dtype=np.float32)
        b = np.zeros((n_rows, rank), dtype=np.float32)
        eye = np.eye(rank, dtype=np.float32)
        for i in range(n_rows):
            s, e = indptr[i], indptr[i + 1]
            n = e - s
            if n == 0:
                A[i] = G + reg * eye
                continue
            F = fixed[cols[s:e]]           # [n, r] gather
            c1 = alpha * w[s:e]            # c - 1
            A[i] = G + (F * c1[:, None]).T @ F + (reg * n) * eye
            b[i] = (c1 + 1.0) @ F
        return np.linalg.solve(A, b[..., None])[..., 0].astype(np.float32)

    t0 = time.monotonic()
    U = half_step(V, u_indptr, u_cols, u_vals, n_users)
    V = half_step(U, i_indptr, i_cols, i_vals, n_items)
    dt = time.monotonic() - t0
    return nnz / dt


def eval_ndcg_at_k(U, V, train_users, train_items, test_users, test_items,
                   n_items: int, k: int = 10, sample: int = 2048,
                   seed: int = 5) -> float:
    """NDCG@k of the trained factors on a held-out slice (binary
    relevance, train items masked out of the ranking) — closes the
    quality loop on the SAME device-trained factors the bench times
    (role of the reference template's MetricEvaluator quality check,
    ``Evaluation.scala:32-89``)."""
    import jax
    import jax.numpy as jnp

    users = np.unique(test_users)
    rng = np.random.default_rng(seed)
    if len(users) > sample:
        users = rng.choice(users, size=sample, replace=False)
    users = np.sort(users)
    row_of = {int(u): j for j, u in enumerate(users)}
    S = len(users)

    # top-(k + max_train) then host-filter the train items: masking the
    # [S, n_items] score matrix on device would need a huge scatter
    sel_tr = np.isin(train_users, users)
    tr_u = train_users[sel_tr]
    tr_i = train_items[sel_tr]
    counts = np.bincount(tr_u, minlength=0)
    max_tr = int(counts.max(initial=0))
    k_fetch = min(k + max_tr, n_items)

    @jax.jit
    def topk(U_s, V_all):
        scores = U_s @ V_all.T
        mask = jnp.arange(V_all.shape[0]) < n_items
        scores = jnp.where(mask[None, :], scores, -jnp.inf)
        return jax.lax.top_k(scores, k_fetch)[1]

    ids = np.asarray(topk(jnp.asarray(U)[jnp.asarray(users)],
                          jnp.asarray(V)))
    train_sets = [set() for _ in range(S)]
    for u, i in zip(tr_u, tr_i):
        train_sets[row_of[int(u)]].add(int(i))
    test_sets = [set() for _ in range(S)]
    for u, i in zip(test_users, test_items):
        j = row_of.get(int(u))
        if j is not None:
            test_sets[j].add(int(i))

    from predictionio_tpu.controller.metric import ndcg_at_k

    total = 0.0
    for j in range(S):
        ranked = [int(i) for i in ids[j]
                  if int(i) not in train_sets[j]][:k]
        score = ndcg_at_k(ranked, test_sets[j], k)
        total += score if score is not None else 0.0
    return total / max(S, 1)


def main():
    scale = float(os.environ.get("BENCH_SCALE", "1.0"))
    cpu_scale = float(os.environ.get("BENCH_CPU_SCALE", "0.1"))
    n_users = int(138_000 * scale)
    n_items = int(27_000 * scale)
    nnz = int(20_000_000 * scale)
    rank = int(os.environ.get("BENCH_RANK", "64"))
    iterations = 5
    alpha, reg = 40.0, 0.01

    import jax

    # one process owns the chip: everything below runs in THIS process,
    # and a run that finds no chip fails instead of timing a CPU
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench.py needs a TPU backend; JAX found {devs}")
    sys.stderr.write(f"devices: {devs}\n")

    from predictionio_tpu.models.als import (
        ALSParams,
        RatingsCOO,
        als_flops_per_iter,
        pack_ratings,
        train_als,
    )

    rng = np.random.default_rng(0)
    # zipf-ish popularity for items, uniform users — MovieLens-like skew
    items = (np.random.default_rng(1).zipf(1.3, size=nnz) % n_items).astype(np.int32)
    users = rng.integers(0, n_users, nnz).astype(np.int32)
    vals = np.ones(nnz, dtype=np.float32)
    ratings = RatingsCOO(users, items, vals, n_users, n_items)

    # bucketed layout: every rating trains, whatever the skew (0 drops)
    params = ALSParams(rank=rank, num_iterations=1, implicit_prefs=True,
                       alpha=alpha, reg=reg, seed=3)

    # pack once (the COO→device transfer + sort; sweeps amortize this),
    # then warm up the compiled half-steps
    packed = pack_ratings(ratings, params)
    def kept_entries(h):
        if hasattr(h, "buckets"):  # BucketedHistories
            return sum(int(np.asarray(b.counts, dtype=np.int64).sum())
                       for b in h.buckets)
        return int(np.asarray(h.counts, dtype=np.int64).sum())

    dropped = 2 * nnz - kept_entries(packed[0]) - kept_entries(packed[1])
    assert dropped == 0, f"bench must train on all ratings; dropped={dropped}"

    peak = device_peak_flops()

    gather = os.environ.get("BENCH_GATHER", "float32")

    def timed_run(rank_r: int, repeats: int = 3):
        """One training configuration at ``rank_r`` over the shared
        packing (the layouts are rank-independent): a warm run, then
        the best of ``repeats`` timed ones."""
        p_run = ALSParams(rank=rank_r, num_iterations=iterations,
                          implicit_prefs=True, alpha=alpha, reg=reg,
                          seed=3, gather_dtype=gather)
        U, V = train_als(ratings, p_run, packed=packed)  # warm
        V.block_until_ready()
        best_dt = float("inf")
        for _ in range(repeats):
            t0 = time.monotonic()
            U, V = train_als(ratings, p_run, packed=packed)
            V.block_until_ready()
            best_dt = min(best_dt, time.monotonic() - t0)
        fl = als_flops_per_iter(packed[0], packed[1], p_run)
        ach = fl * iterations / best_dt  # raw; display-rounded once
        out = {
            "value": round(nnz * iterations / best_dt, 1),
            "achieved_tflops": round(ach / 1e12, 2),
            "mfu": round(ach / peak, 4) if peak else None,
            "gather_dtype": gather,
            "_achieved_flops_raw": ach,
        }
        return out, best_dt, p_run

    r64, dt, params_run = timed_run(rank)
    ratings_per_sec = nnz * iterations / dt
    achieved_flops = r64.pop("_achieved_flops_raw")
    mfu = r64["mfu"]

    # rank-128 datapoint: the layouts are rank-independent, so the same
    # packing times a rank where the MXU is naturally fuller
    rank128 = None
    if os.environ.get("BENCH_RANK128", "1") == "1" and rank != 128:
        rank128, _, _ = timed_run(128, repeats=2)
        rank128.pop("_achieved_flops_raw", None)

    cpu_rps = cpu_als_baseline(
        n_users=max(int(n_users * cpu_scale), 64),
        n_items=max(int(n_items * cpu_scale), 64),
        nnz=max(int(nnz * cpu_scale), 4096),
        rank=rank, alpha=alpha, reg=reg)

    # quality loop (VERDICT r2 task 7): hold out ~1%, retrain on the
    # rest with the SAME params/device path, NDCG@10 on the holdout
    ndcg10 = None
    if os.environ.get("BENCH_SKIP_QUALITY") != "1":
        rng_q = np.random.default_rng(11)
        test_sel = rng_q.random(nnz) < 0.01
        tr = RatingsCOO(users[~test_sel], items[~test_sel],
                        vals[~test_sel], n_users, n_items)
        Uq, Vq = train_als(tr, params_run)
        Vq.block_until_ready()
        ndcg10 = round(eval_ndcg_at_k(
            Uq, Vq, tr.users, tr.items, users[test_sel],
            items[test_sel], n_items=n_items), 4)

    # The sub-batteries below all run IN THIS PROCESS (it owns the chip;
    # a child that needed it would fail or hang), and a battery that
    # fails fails the run: nothing is attempted and swallowed. Three
    # former batteries are not on the line any more — the cold-start
    # drill and the columnar-ingest race shelled out to scripts that pin
    # the CPU (their numbers do not belong under a TPU device label;
    # they stay CI drills), and the roofline probe shelled out to
    # children that need the chip this process holds.
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))

    # serving-latency probe: the engine server's device path, ~200 HTTP
    # queries through the REAL deployed stack (CreateServer.scala:484-633
    # role), micro-batcher off vs on.
    serving = None
    if os.environ.get("BENCH_SERVING", "1") == "1":
        import serving_bench as sb

        n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", "200"))
        n_cat = int(os.environ.get("BENCH_SERVE_ITEMS", "1200000"))
        hi_threads = int(os.environ.get("BENCH_SERVE_THREADS_HI", "256"))
        # host fast path + per-query trickle + the apples-to-apples
        # burst pair (per-query vs micro-batcher at the same offered
        # concurrency) — one battery definition, shared with
        # serving_bench.main
        serving = sb.standard_battery(n_cat, 64, n_req, 8, hi_threads)
        # quantized-lane side-by-side (ISSUE 13): the same device
        # per-query + micro-batch workload with serving_quant on,
        # against the battery's f32 rows — the `serving_quant` summary
        # row lands in the BENCH line
        q_dtype = os.environ.get("BENCH_SERVING_QUANT", "int8")
        if q_dtype in ("bf16", "int8"):
            qrows = sb.quant_battery(
                n_cat, 64, n_req, 8, hi_threads, q_dtype,
                f32_per_query=serving.get("per_query"),
                f32_micro=serving.get("microbatch"))
            serving["serving_quant"] = qrows[-1]
            serving["quant_rows"] = qrows[:-1]

    # per-mode device-scaling block (ISSUE 6): the same burst workload
    # through the micro-batcher in single / replicated / sharded serving
    # — replicated's scaling_x against the single lane is the
    # near-linear-on-N-devices acceptance number
    device_scaling = None
    if os.environ.get("BENCH_MESH", "1") == "1":
        if len(jax.devices()) > 1:
            import serving_bench as sb_mesh

            device_scaling = sb_mesh.mesh_scaling_battery(
                int(os.environ.get("BENCH_SERVE_ITEMS", "1200000")), 64,
                int(os.environ.get("BENCH_SERVE_REQUESTS", "200")),
                int(os.environ.get("BENCH_SERVE_THREADS_HI", "256")))
        else:
            device_scaling = {"devices": 1,
                              "note": "one device visible; no "
                                      "fan-out to measure"}

    # streaming freshness (ISSUE 10): the real ingest→fold-in→serve
    # loop over HTTP — event→servable p50 is the freshness the
    # incremental trainer actually delivers vs the ~minutes a full
    # retrain cadence bounds it to
    streaming = None
    if os.environ.get("BENCH_STREAMING", "1") == "1":
        import streaming_smoke as stream_smoke

        streaming = stream_smoke.measure(
            trials=int(os.environ.get("BENCH_STREAM_TRIALS", "6")))

    # capacity model (ISSUE 15): the mixed-traffic load harness —
    # Zipf queries + event ingest + streaming fold-ins + a held canary
    # concurrently, offered rate swept to the knee per serving config,
    # freshness re-measured at 80% of the knee WHILE queries fly (the
    # number beside the idle event_to_servable_ms)
    capacity = None
    if os.environ.get("BENCH_CAPACITY", "1") == "1":
        import load_harness

        capacity = load_harness.measure(
            configs=os.environ.get("BENCH_CAPACITY_CONFIGS",
                                   "host,staged,cached"),
            rate_min=float(os.environ.get("BENCH_CAPACITY_RATE_MIN", "8")),
            rate_max=float(os.environ.get("BENCH_CAPACITY_RATE_MAX",
                                          "128")),
            step_sec=float(os.environ.get("BENCH_CAPACITY_STEP_SEC", "4")),
            freshness_trials=3)

    # elastic reliability (ISSUE 11): the serving lane-kill drill —
    # inject a dead replicated lane under real HTTP load, require zero
    # failed in-deadline queries, and measure the recovery-time-
    # objective (lane death → lane rejoined) from the server's own
    # degraded transitions (skipped, and says so, with one device)
    reliability = None
    if os.environ.get("BENCH_RELIABILITY", "1") == "1":
        import reliability_smoke as rel_smoke

        reliability = rel_smoke.measure()

    # telemetry tails (ISSUE 2): surface the serving battery's scraped
    # server-side signals as top-level keys so the perf trajectory
    # captures recompiles / hidden transfers / p99, not just means
    def _tele(cfg_key: str, field: str):
        tele = ((serving or {}).get(cfg_key) or {}).get("telemetry") or {}
        return tele.get(field)

    tele_cfg = "microbatch" if (serving or {}).get("microbatch") \
        else "per_query"

    print(json.dumps({
        "metric": "als_implicit_train_throughput",
        "value": round(ratings_per_sec, 1),
        "unit": "ratings/s/iter",
        "vs_baseline": round(ratings_per_sec / cpu_rps, 3),
        "mfu": mfu,
        "achieved_tflops": round(achieved_flops / 1e12, 2),
        "cpu_baseline_measured": round(cpu_rps, 1),
        "dropped_entries": dropped,
        "ndcg10": ndcg10,
        "rank": rank,
        "gather_dtype": r64.get("gather_dtype"),
        "rank128": rank128,
        "device_scaling": device_scaling,
        "serving_p50_ms": (serving or {}).get(
            "per_query", {}).get("p50_ms"),
        "serving_p99_ms": (serving or {}).get(
            "per_query", {}).get("p99_ms"),
        "compiles_since_warm": _tele(tele_cfg, "compilesSinceWarm"),
        "transfer_guard_violations": _tele(tele_cfg,
                                           "transferGuardViolations"),
        # flight-recorder overhead (ISSUE 12 acceptance ≤5%): host
        # fast-path p50 with tracing on vs off, same load
        "trace_overhead_pct": (serving or {}).get("trace_overhead_pct"),
        # quantized serving lane vs the f32 einsum lane at the same
        # offered load (ISSUE 13): per-query p50 pair + micro-batch
        # qps/p99 ratios
        "serving_quant": (serving or {}).get("serving_quant"),
        # event→servable freshness through the streaming trainer
        # (ISSUE 10): ingest to correct serve, real HTTP loop
        "event_to_servable_ms": (streaming or {}).get(
            "event_to_servable_p50_ms"),
        # the same freshness number measured at 80% of the staged
        # config's knee qps WITH queries in flight (ISSUE 15): the
        # idle number above says what the trainer can do, this one
        # says what it does while the server earns its keep
        "event_to_servable_under_load_ms": (
            ((capacity or {}).get("configs") or {})
            .get("staged", {}).get("freshness_under_load_ms")),
        "streaming": streaming,
        # the capacity model (ISSUE 15): knee qps + p99 at 80% of knee
        # per serving config under MIXED traffic — what `ptpu slo
        # check` gates against the committed slo/specs/ci.json
        "capacity": capacity,
        # lane-kill recovery-time-objective (ISSUE 11): degraded-mode
        # entry→exit with zero failed in-deadline queries required
        "rto_ms": (reliability or {}).get("rto_ms"),
        "reliability": reliability,
        "serving": serving,
        "device": jax.devices()[0].device_kind,
        "platform": jax.devices()[0].platform,
        "device_count": len(jax.devices()),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
    }))


if __name__ == "__main__":
    main()
