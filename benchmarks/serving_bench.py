"""Serving-path benchmark: the REAL engine server under concurrent load.

Measures `POST /queries.json` latency through the full deployed stack
(HTTP → QueryServer → template predict → top-k), the reference hot path
``CreateServer.scala:484-633``, in three configurations:

- ``host``: small catalog — the host fast path (numpy dot, the
  reference's in-JVM BLAS serving role)
- ``device``: a catalog past ``HOST_SERVE_WORK`` — every query is an
  MXU matmul + top-k dispatch
- ``device+batching``: same catalog with the serving micro-batcher
  coalescing concurrent queries into one ``batch_predict`` dispatch
  (``ServerConfig(batching=True)``; the reference served strictly
  per-request — ``CreateServer.scala:507-510`` "TODO: Parallelize")

Prints ONE JSON line with p50/p90/p99 (ms) and throughput per config.

With ``--canary FRACTION``, an extra config binds a second synthetic
model as a CANDIDATE release at that traffic fraction (the rollout
splitter's hash-of-entity cohort, health gate held) and reports
stable-vs-candidate p50/p99 side by side from the server's own per-arm
release series — the canary latency-overhead view.

With ``--zipf ALPHA``, the workload's users are drawn from a Zipf(α)
distribution instead of uniform — the hot-entity skew production
recommendation traffic actually has. With ``--cache`` (ISSUE 4), the
device per-query config runs TWICE on that skewed workload — serving
cache off vs on — and a trailing hot-query loop measures the pure
cache-hit latency; the emitted row reports cached-vs-uncached p50/p99
side by side plus the server's own /cache.json tier stats.

With ``--mesh`` (ISSUE 6), a device-scaling battery runs the same
burst workload against the micro-batcher in single mode, replicated
fan-out (a full model copy per device, per-device lanes), and the
row-sharded mesh — per-mode qps plus the replicated/single
``scaling_x`` ratio.

With ``--arrival-rate QPS``, an OPEN-LOOP fixed-rate generator replaces
the closed-loop battery (coordinated-omission-safe: latency is measured
from each request's scheduled arrival, so a stalling server accrues
latency instead of silently slowing the offered load). Sweep the rate
to trace the qps-vs-p99 knee — the first slice of ROADMAP's
load-harness item.

With ``--quant DTYPE`` (ISSUE 13), the device per-query and
micro-batch configs run again with row-quantized serving tables
(``serving_quant=DTYPE``) and a
``serving_quant`` summary row reports quantized-vs-f32 per-query p50
and micro-batch qps/p99 ratios side by side — the row ``bench.py``
embeds in the BENCH line.

Usage: python benchmarks/serving_bench.py [n_items_device] [rank]
                                          [--canary FRACTION]
                                          [--zipf ALPHA] [--cache]
                                          [--mesh] [--quant DTYPE]
                                          [--arrival-rate QPS]
Env:   SERVE_THREADS (8), SERVE_REQUESTS (400 per config)
"""

from __future__ import annotations

import json
import os
import sys
import time
import urllib.request
from datetime import datetime, timezone

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _loadgen import (  # noqa: E402
    expect_json_field,
    json_post_sender,
    run_load,
    sample_entities,
)
from predictionio_tpu.controller import Context  # noqa: E402
from predictionio_tpu.data.bimap import BiMap  # noqa: E402
from predictionio_tpu.data.storage import App, Storage  # noqa: E402
from predictionio_tpu.data.storage.base import (  # noqa: E402
    EngineInstance,
    STATUS_COMPLETED,
)
from predictionio_tpu.models.als import (  # noqa: E402
    ALSModel,
    ALSParams,
    HOST_SERVE_WORK,
)
from predictionio_tpu.server.engineserver import (  # noqa: E402
    QueryServer,
    ServerConfig,
    create_engine_server,
)
from predictionio_tpu.templates.recommendation import (  # noqa: E402
    default_engine_params,
    recommendation_engine,
)


def synth_model(n_users: int, n_items: int, rank: int,
                device: bool) -> ALSModel:
    rng = np.random.default_rng(0)
    U = rng.standard_normal((n_users, rank)).astype(np.float32)
    V = rng.standard_normal((n_items, rank)).astype(np.float32)
    if device:
        import jax
        U = jax.device_put(U)
        V = jax.device_put(V)
        V.block_until_ready()
    return ALSModel(
        user_factors=U, item_factors=V, n_users=n_users, n_items=n_items,
        user_ids=BiMap({f"u{i}": i for i in range(n_users)}),
        item_ids=BiMap({f"i{i}": i for i in range(n_items)}),
        params=ALSParams(rank=rank))


#: Zipf-or-uniform user draw — shared with the load harness
_sample_users = sample_entities


def _query_sender(port: int, users: np.ndarray, shed=()):
    """One keep-alive worker posting ``/queries.json`` for user k.
    ``shed`` lists statuses counted as load-shedding instead of
    errors (the open-loop knee sweep passes ``(503,)``; the
    closed-loop battery treats every non-200 as a failure)."""
    return json_post_sender(
        port, "/queries.json",
        body_fn=lambda k: json.dumps({"user": f"u{users[k]}",
                                      "num": 10}).encode(),
        check=expect_json_field("itemScores"), shed_status=shed)


def _boot_server(model: ALSModel, cfg: ServerConfig):
    """One deployed QueryServer over a synthetic COMPLETED instance —
    shared by the closed-loop configs and the open-loop generator."""
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "servebench"))
    ctx = Context(app_name="servebench", _storage=storage)
    engine = recommendation_engine()
    ep = default_engine_params("servebench", rank=model.params.rank)
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="bench", status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="bench", engine_version="1",
        engine_variant="engine.json", engine_factory="synthetic")
    qs = QueryServer(ctx, engine, ep, [model], inst, cfg)
    srv = create_engine_server(qs, host="127.0.0.1", port=0)
    srv.start_background()
    return qs, srv


def _wait_warm(port: int, label: str) -> None:
    """Block until the server-side warmup (ServerConfig.warm_start
    compiles the single-query + pow2 batch ladder) reports done."""
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status.json",
                timeout=30) as resp:
            if json.loads(resp.read()).get("servingWarm"):
                return
        time.sleep(0.5)
    raise RuntimeError(f"{label}: serving warmup did not finish")


def bench_config(model: ALSModel, cfg: ServerConfig, n_requests: int,
                 n_threads: int, label: str, zipf=None,
                 hot_hit_probe: int = 0) -> dict:
    qs, srv = _boot_server(model, cfg)
    port = srv.port
    rng = np.random.default_rng(1)
    users = _sample_users(rng, model.n_users, n_requests, zipf)

    _wait_warm(port, label)
    for u in users[:3]:
        body = json.dumps({"user": f"u{u}", "num": 10}).encode()
        urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json", data=body,
            headers={"Content-Type": "application/json"}), timeout=120
        ).read()

    # closed-loop burst through the shared generator (_loadgen): one
    # keep-alive connection per worker, latency from each send
    stats, wall = run_load(_query_sender(port, users), n_requests,
                           n_threads)
    lat, errors = stats.lat, stats.errors
    # hot-query probe (ISSUE 4): with the serving cache on, repeat ONE
    # hot user's query sequentially — after the first fill these are
    # pure cache hits, measuring the parse→cache→respond floor the
    # acceptance gate compares against the uncached device p50
    hot_hit = None
    if hot_hit_probe > 0:
        import http.client

        hot_body = json.dumps({"user": f"u{users[0]}",
                               "num": 10}).encode()
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=120)
        try:
            hot_lat = []
            for i in range(hot_hit_probe + 1):
                t0 = time.monotonic()
                conn.request("POST", "/queries.json", body=hot_body,
                             headers={"Content-Type":
                                      "application/json"})
                conn.getresponse().read()
                if i > 0:  # drop the (possible) fill miss
                    hot_lat.append(time.monotonic() - t0)
        finally:
            conn.close()
        arr_h = np.sort(np.asarray(hot_lat)) * 1e3
        hot_hit = {
            "n": len(arr_h),
            "p50_ms": round(float(np.percentile(arr_h, 50)), 3),
            "p99_ms": round(float(np.percentile(arr_h, 99)), 3),
        }
    cache_stats = None
    if cfg.serving_cache:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/cache.json",
                    timeout=30) as resp:
                tiers = json.loads(resp.read()).get("tiers") or {}
            cache_stats = {
                name: {"hits": t.get("hits"), "misses": t.get("misses"),
                       "hitRatio": round(t.get("hitRatio", 0.0), 4)}
                for name, t in tiers.items()}
        except Exception as e:  # noqa: BLE001 — stats are advisory
            cache_stats = {"error": str(e)[:200]}
    # scrape the server's own telemetry BEFORE shutdown (ISSUE 2): the
    # emitted bench line carries compilesSinceWarm + transfer-guard
    # violations so the perf trajectory captures recompile storms and
    # hidden host syncs, not just client-side latency
    telemetry = None
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status.json",
                timeout=30) as resp:
            status = json.loads(resp.read())
        lat_hist = status.get("latency") or {}
        telemetry = {
            "compilesSinceWarm":
                (status.get("recompile") or {}).get("compilesSinceWarm"),
            "transferGuardViolations":
                status.get("transferGuardViolations"),
            "server_p99_ms": (round(lat_hist["p99"] * 1000, 2)
                              if lat_hist.get("p99") is not None
                              else None),
            # the pipeline overlap proof (ISSUE 9): device idle /
            # overlap fractions + deadline sheds from the server's own
            # accounting, embedded beside the client-side percentiles
            "pipeline": status.get("pipeline"),
        }
    except Exception as e:  # noqa: BLE001 — telemetry is advisory
        telemetry = {"error": str(e)[:200]}
    srv.shutdown()
    if errors or not lat:
        raise RuntimeError(
            f"{label}: {len(errors)} failed requests of {n_requests} "
            f"(first: {errors[0] if errors else 'none'}) — latency "
            f"numbers would describe a degraded load, refusing")
    arr = np.sort(np.asarray(lat)) * 1e3
    out = {
        "config": label,
        "n": len(arr),
        "p50_ms": round(float(np.percentile(arr, 50)), 2),
        "p90_ms": round(float(np.percentile(arr, 90)), 2),
        "p99_ms": round(float(np.percentile(arr, 99)), 2),
        "qps": round(len(arr) / wall, 1),
        "telemetry": telemetry,
    }
    if zipf is not None:
        out["zipf"] = float(zipf)
    if hot_hit is not None:
        out["hot_hit"] = hot_hit
    if cache_stats is not None:
        out["cache"] = cache_stats
    return out


def standard_battery(n_items_dev: int, rank: int, n_req: int,
                     n_threads: int, hi_threads: int) -> dict:
    """The serving battery — ONE definition shared by this script's
    ``main()`` and ``bench.py``'s serving block (they drifted when each
    kept its own copy): host fast path, per-query at trickle load,
    per-query and micro-batcher at burst load (``hi_threads`` offered
    concurrency — the apples-to-apples pair)."""
    from predictionio_tpu.server.engineserver import ServerConfig

    host_model = synth_model(2000, 2000, rank, device=False)
    dev_model = synth_model(50_000, n_items_dev, rank, device=True)
    hi_req = max(n_req, 8 * hi_threads)
    out = {
        "host_fast_path": bench_config(
            host_model, ServerConfig(), max(n_req, 300), n_threads,
            "host_fast_path"),
        # tracing A/B (ISSUE 12 acceptance: tracing adds ≤5% to the
        # host fast-path p50): the same load with the flight recorder
        # off — the ONLY config difference
        "host_fast_path_untraced": bench_config(
            host_model, ServerConfig(tracing=False), max(n_req, 300),
            n_threads, "host_fast_path_untraced"),
        "per_query": bench_config(
            dev_model, ServerConfig(), n_req, n_threads,
            "device_per_query"),
        "per_query_loaded": bench_config(
            dev_model, ServerConfig(), hi_req, hi_threads,
            "device_per_query_loaded"),
        "microbatch": bench_config(
            dev_model, ServerConfig(batching=True, max_batch=128,
                                    batch_window_ms=2.0),
            hi_req, hi_threads, "device_microbatch_staged"),
    }
    traced = out["host_fast_path"].get("p50_ms")
    untraced = out["host_fast_path_untraced"].get("p50_ms")
    if traced and untraced:
        out["trace_overhead_pct"] = round(
            (traced / untraced - 1.0) * 100.0, 2)
    return out


def bench_open_loop(model: ALSModel, cfg: ServerConfig, rate_qps: float,
                    n_requests: int, n_threads: int, label: str) -> dict:
    """Open-loop fixed-rate load (the first slice of ROADMAP's
    load-harness item): request k's INTENDED start time is
    ``t0 + k/rate`` regardless of how the server is doing, and latency
    is measured from that intended start — coordinated-omission-safe:
    a stalling server keeps accruing latency on every scheduled
    arrival instead of silently slowing the offered load the way a
    closed loop does. Sweep ``--arrival-rate`` to find the qps-vs-p99
    knee; past it, p99 grows without bound (or deadline sheds appear),
    which IS the capacity signal."""
    qs, srv = _boot_server(model, cfg)
    port = srv.port
    try:
        _wait_warm(port, label)
        rng = np.random.default_rng(3)
        users = rng.integers(0, model.n_users, n_requests)
        for u in users[:3]:
            body = json.dumps({"user": f"u{u}", "num": 10}).encode()
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/queries.json", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=120).read()

        # the open-loop discipline lives in _loadgen.run_load now:
        # request k's intended start is t0 + k/rate and latency is
        # measured from that schedule (coordinated-omission-safe)
        stats, wall = run_load(
            _query_sender(port, users, shed=(503,)), n_requests,
            n_threads, rate_qps=rate_qps)
        lat, shed, errors = stats.lat, stats.shed, stats.errors
        pipe = None
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/status.json",
                    timeout=30) as resp:
                pipe = json.loads(resp.read()).get("pipeline")
        except Exception as e:  # noqa: BLE001 — telemetry is advisory
            pipe = {"error": str(e)[:200]}
    finally:
        srv.shutdown()
    if errors:
        raise RuntimeError(
            f"{label}: {len(errors)} failed requests "
            f"(first: {errors[0]})")
    if not lat:
        raise RuntimeError(f"{label}: every request was shed; offered "
                           f"rate {rate_qps} is far past the knee")
    arr = np.sort(np.asarray(lat)) * 1e3
    return {
        "config": label,
        "open_loop": True,
        "offered_qps": rate_qps,
        "achieved_qps": round(len(lat) / wall, 1),
        "n": len(arr),
        "shed": len(shed),
        "p50_ms": round(float(np.percentile(arr, 50)), 2),
        "p90_ms": round(float(np.percentile(arr, 90)), 2),
        "p99_ms": round(float(np.percentile(arr, 99)), 2),
        "pipeline": pipe,
    }


def mesh_scaling_battery(n_items_dev: int, rank: int, n_req: int,
                         hi_threads: int) -> dict:
    """Per-mode device-scaling probe (ISSUE 6): the SAME burst workload
    against the micro-batcher in single mode, replicated fan-out
    (per-device lanes), and the row-sharded mesh — qps side by side
    plus ``scaling_x`` (replicated qps over single-lane qps, the
    near-linear-on-N-devices acceptance number). One device degrades
    to the single row alone."""
    import jax

    n_dev = len(jax.devices())
    dev_model = synth_model(50_000, n_items_dev, rank, device=True)
    hi_req = max(n_req, 8 * hi_threads)
    single = bench_config(
        dev_model, ServerConfig(batching=True, max_batch=128,
                                batch_window_ms=2.0),
        hi_req, hi_threads, "mesh_single_microbatch")
    out: dict = {"devices": n_dev, "single": single}
    if n_dev > 1:
        rep = bench_config(
            dev_model, ServerConfig(batching=True, max_batch=128,
                                    batch_window_ms=2.0,
                                    serving_mode="replicated"),
            hi_req, hi_threads, "mesh_replicated_microbatch")
        if single.get("qps"):
            rep["scaling_x"] = round(rep["qps"] / single["qps"], 2)
        out["replicated"] = rep
        sharded = bench_config(
            dev_model, ServerConfig(batching=True, max_batch=128,
                                    batch_window_ms=2.0,
                                    serving_mode="sharded"),
            n_req, min(hi_threads, 64), "mesh_sharded_microbatch")
        out["sharded"] = sharded
    return out


def bench_canary(model: ALSModel, candidate: ALSModel, fraction: float,
                 n_requests: int, n_threads: int) -> dict:
    """Stable + candidate bound side by side: the canary splitter
    routes ``fraction`` of the cohort to the candidate while the gate
    is held open (no ramp), then both arms' server-side latency series
    are reported together."""
    from predictionio_tpu.rollout import HealthPolicy

    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "servebench"))
    ctx = Context(app_name="servebench", _storage=storage)
    engine = recommendation_engine()
    ep = default_engine_params("servebench", rank=model.params.rank)
    now = datetime.now(timezone.utc)
    for iid in ("bench-stable", "bench-cand"):
        storage.engine_instances().insert(EngineInstance(
            id=iid, status=STATUS_COMPLETED, start_time=now,
            end_time=now, engine_id="bench", engine_version="1",
            engine_variant="engine.json", engine_factory="synthetic"))
    qs = QueryServer(ctx, engine, ep, [model],
                     storage.engine_instances().get("bench-stable"),
                     ServerConfig())
    srv = create_engine_server(qs, host="127.0.0.1", port=0)
    srv.start_background()
    port = srv.port
    try:
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/status.json",
                    timeout=30) as resp:
                if json.loads(resp.read()).get("servingWarm"):
                    break
            time.sleep(0.5)
        # hold the gate open for the whole bench: no ramp, no verdict
        qs.start_canary("bench-cand", fraction=fraction,
                        policy=HealthPolicy(window_sec=3600,
                                            min_queries=1 << 30),
                        models=[candidate], actor="serving-bench")
        qs._candidate.warm_done.wait(timeout=300)

        rng = np.random.default_rng(2)
        users = rng.integers(0, model.n_users, n_requests)
        stats, _wall = run_load(_query_sender(port, users),
                                n_requests, n_threads)
        errors = stats.errors
        arms = qs.release_arms()
    finally:
        srv.shutdown()
    if errors:
        raise RuntimeError(
            f"canary bench: {len(errors)} failed requests "
            f"(first: {errors[0]})")

    def arm_row(arm: dict) -> dict:
        lat = arm.get("latency") or {}
        return {
            "queries": arm["queries"],
            "errors": arm["errors"],
            "p50_ms": (round(lat["p50"] * 1000, 2)
                       if lat.get("p50") is not None else None),
            "p99_ms": (round(lat["p99"] * 1000, 2)
                       if lat.get("p99") is not None else None),
        }

    return {
        "config": "canary_split",
        "fraction": fraction,
        "stable": arm_row(arms["stable"]),
        "candidate": arm_row(arms["candidate"]),
    }


def quant_battery(n_items_dev: int, rank: int, n_req: int,
                  n_threads: int, hi_threads: int, quant: str,
                  f32_per_query: dict | None = None,
                  f32_micro: dict | None = None) -> list:
    """The --quant view (ISSUE 13): the SAME workload against the
    device per-query path and the micro-batched lane with
    ``serving_quant=DTYPE``, side by side with the f32 lane — reusing
    the standard battery's f32 rows when the caller already measured
    them. Emits a ``serving_quant`` summary row (embedded in the BENCH
    line): the acceptance view is the quantized lane beating the f32
    lane on the benched path at equal p99."""
    dev_model = synth_model(50_000, n_items_dev, rank, device=True)
    hi_req = max(n_req, 8 * hi_threads)
    rows = []
    if f32_per_query is None:
        f32_per_query = bench_config(
            dev_model, ServerConfig(), n_req, n_threads,
            "device_per_query")
        rows.append(f32_per_query)
    if f32_micro is None:
        f32_micro = bench_config(
            dev_model, ServerConfig(batching=True, max_batch=128,
                                    batch_window_ms=2.0),
            hi_req, hi_threads, "device_microbatch_staged")
        rows.append(f32_micro)
    q_per_query = bench_config(
        dev_model, ServerConfig(serving_quant=quant), n_req,
        n_threads, f"device_per_query_{quant}")
    q_micro = bench_config(
        dev_model, ServerConfig(batching=True, max_batch=128,
                                batch_window_ms=2.0,
                                serving_quant=quant),
        hi_req, hi_threads, f"device_microbatch_{quant}")
    rows += [q_per_query, q_micro]
    summary = {
        "config": "serving_quant",
        "quant": quant,
        "per_query_f32_p50_ms": f32_per_query.get("p50_ms"),
        "per_query_quant_p50_ms": q_per_query.get("p50_ms"),
        "micro_f32_qps": f32_micro.get("qps"),
        "micro_quant_qps": q_micro.get("qps"),
        "micro_f32_p99_ms": f32_micro.get("p99_ms"),
        "micro_quant_p99_ms": q_micro.get("p99_ms"),
    }
    if f32_micro.get("qps") and q_micro.get("qps"):
        summary["qps_x"] = round(q_micro["qps"] / f32_micro["qps"], 2)
    if f32_micro.get("p99_ms") and q_micro.get("p99_ms"):
        summary["p99_x"] = round(
            f32_micro["p99_ms"] / q_micro["p99_ms"], 2)
    rows.append(summary)
    return rows


def bench_cached_pair(n_items_dev: int, rank: int, n_req: int,
                      n_threads: int, zipf) -> list:
    """The --cache view: the SAME Zipf-skewed workload against the
    device per-query config with the serving cache off vs on, plus the
    pure cache-hit probe — cached-vs-uncached p50/p99 side by side."""
    dev_model = synth_model(50_000, n_items_dev, rank, device=True)
    uncached = bench_config(
        dev_model, ServerConfig(), n_req, n_threads,
        "device_per_query_zipf", zipf=zipf)
    cached_cfg = ServerConfig(
        serving_cache=True, cache_ttl_sec=600.0,
        hot_entities=512, hot_refresh_every=64)
    cached = bench_config(
        dev_model, cached_cfg, n_req, n_threads,
        "device_per_query_cached", zipf=zipf,
        hot_hit_probe=max(100, n_req // 4))
    hit_p50 = (cached.get("hot_hit") or {}).get("p50_ms")
    if hit_p50 is not None and uncached["p50_ms"]:
        # the acceptance ratio: hot-query (cache-hit) p50 against the
        # UNCACHED device per-query p50
        cached["hit_vs_uncached_p50"] = round(
            hit_p50 / uncached["p50_ms"], 4)
    return [uncached, cached]


def main() -> None:
    argv = sys.argv[1:]
    canary_fraction = None
    if "--canary" in argv:
        i = argv.index("--canary")
        canary_fraction = float(argv[i + 1])
        del argv[i:i + 2]
    zipf_alpha = None
    if "--zipf" in argv:
        i = argv.index("--zipf")
        zipf_alpha = float(argv[i + 1])
        del argv[i:i + 2]
    with_cache = False
    if "--cache" in argv:
        with_cache = True
        argv.remove("--cache")
    with_mesh = False
    if "--mesh" in argv:
        with_mesh = True
        argv.remove("--mesh")
    arrival_rate = None
    if "--arrival-rate" in argv:
        i = argv.index("--arrival-rate")
        arrival_rate = float(argv[i + 1])
        del argv[i:i + 2]
    quant = None
    if "--quant" in argv:
        i = argv.index("--quant")
        quant = argv[i + 1]
        del argv[i:i + 2]
        if quant not in ("bf16", "int8"):
            raise SystemExit(f"--quant must be bf16 or int8, "
                             f"got {quant!r}")
    sys.argv[1:] = argv
    n_items_dev = int(sys.argv[1]) if len(sys.argv) > 1 else 1_200_000
    rank = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    n_threads = int(os.environ.get("SERVE_THREADS", "8"))
    n_requests = int(os.environ.get("SERVE_REQUESTS", "400"))

    assert n_items_dev * rank > HOST_SERVE_WORK, \
        "device catalog must exceed HOST_SERVE_WORK to force the MXU path"

    import jax

    from predictionio_tpu.utils.platform import force_cpu_if_requested
    force_cpu_if_requested()
    device_kind = jax.devices()[0].device_kind

    hi = int(os.environ.get("SERVE_THREADS_HI", "256"))
    if arrival_rate is not None:
        # open-loop mode REPLACES the closed-loop battery: fixed-rate
        # arrivals against the micro-batch path — sweep the rate to
        # trace the knee
        from predictionio_tpu.server.engineserver import ServerConfig

        dev_model = synth_model(50_000, n_items_dev, rank, device=True)
        n_open = max(n_requests, int(arrival_rate * 10))
        results = [
            bench_open_loop(
                dev_model, ServerConfig(batching=True, max_batch=128,
                                        batch_window_ms=2.0),
                arrival_rate, n_open, hi, "open_loop_staged"),
        ]
        print(json.dumps({
            "bench": "serving_queries_json_open_loop",
            "device": device_kind,
            "rank": rank,
            "n_items_device": n_items_dev,
            "offered_qps": arrival_rate,
            "results": results,
        }))
        return
    battery = standard_battery(n_items_dev, rank, n_requests,
                               n_threads, hi)
    results = list(battery.values())
    if quant is not None:
        results.extend(quant_battery(
            n_items_dev, rank, n_requests, n_threads, hi, quant,
            f32_per_query=battery.get("per_query"),
            f32_micro=battery.get("microbatch")))
    if with_mesh:
        scaling = mesh_scaling_battery(n_items_dev, rank, n_requests, hi)
        results.append({"config": "mesh_scaling", **scaling})
    if with_cache:
        results.extend(bench_cached_pair(n_items_dev, rank, n_requests,
                                         n_threads, zipf_alpha))
    if canary_fraction is not None:
        dev_model = synth_model(50_000, n_items_dev, rank, device=True)
        cand_model = synth_model(50_000, n_items_dev, rank, device=True)
        results.append(bench_canary(dev_model, cand_model,
                                    canary_fraction,
                                    max(n_requests, 200), n_threads))
    print(json.dumps({
        "bench": "serving_queries_json",
        "device": device_kind,
        "rank": rank,
        "n_items_device": n_items_dev,
        "threads": n_threads,
        "results": results,
    }))


if __name__ == "__main__":
    main()
