"""What the ``open`` and ``closed`` loops share: a real ``QueryServer``
over HTTP in this process, which holds the chip, and the load generators
in child processes that import neither JAX nor the program."""

from __future__ import annotations

import http.client
import json
import multiprocessing
import time

import numpy as np

from .. import data, generators, loadgen, readers, reference, trace
from . import CellBase


def _boot(model, server_config: dict, rank: int):
    """A deployed ``QueryServer`` over a synthetic COMPLETED instance:
    the few lines of ``benchmarks/serving_bench.py::_boot_server``."""
    from datetime import datetime, timezone

    from predictionio_tpu.controller import Context
    from predictionio_tpu.data.storage import App, Storage
    from predictionio_tpu.data.storage.base import (
        STATUS_COMPLETED, EngineInstance)
    from predictionio_tpu.server.engineserver import (
        QueryServer, ServerConfig, create_engine_server)
    from predictionio_tpu.templates.recommendation import (
        default_engine_params, recommendation_engine)

    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "cellbench"))
    ctx = Context(app_name="cellbench", _storage=storage)
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="cellbench", status=STATUS_COMPLETED, start_time=now,
        end_time=now, engine_id="cellbench", engine_version="1",
        engine_variant="engine.json", engine_factory="synthetic")
    # the benchmark warms the cell's own shapes itself (see setup):
    # the program can warm only its whole 45-shape ladder
    cfg = ServerConfig(**server_config, warm_start=False)
    qs = QueryServer(ctx, recommendation_engine(),
                     default_engine_params("cellbench", rank=rank),
                     [model], inst, cfg)
    srv = create_engine_server(qs, host="127.0.0.1", port=0)
    srv.start_background()
    return qs, srv


def _percentile(values, q: float) -> float:
    return float(np.percentile(values, q)) if len(values) else float("nan")


class Cell(CellBase):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.children, self.qs, self.srv = [], None, None
        self.open = self.traffic["loop"] == "open"

    # -- inputs: what the system would be given ---------------------------
    def inputs(self) -> None:
        import jax
        import jax.numpy as jnp

        m, tr = self.config["model"], self.traffic
        self.n_users, self.n_items, self.rank = \
            m["n_users"], m["n_items"], m["rank"]
        self.user_keys = [f"u{i}" for i in range(self.n_users)]
        self.item_keys = [f"i{i}" for i in range(self.n_items)]

        def make(key):
            ku, kv = jax.random.split(key)
            scale = 1.0 / np.sqrt(self.rank)
            return (jax.random.normal(ku, (self.n_users, self.rank),
                                      jnp.float32) * scale,
                    jax.random.normal(kv, (self.n_items, self.rank),
                                      jnp.float32) * scale)

        key = jax.random.fold_in(jax.random.key(self.seed >> 31),
                                 self.seed & 0x7fffffff)
        self.U, self.V = jax.block_until_ready(jax.jit(make)(key))

        G, num = int(tr["generators"]), int(tr["num"])
        rng = np.random.default_rng([self.seed, 0x9e4])
        if self.open:
            due = generators.find(tr["arrivals"]).arrivals(
                tr, self.seconds, self.seed)
            n = len(due)
        else:
            n = G * int(np.ceil(float(tr["max_qps"]) * self.seconds / G))
            due = None
        self.req_users = data.sample_entities(rng, self.n_users, n,
                                              tr.get("zipf"))
        self.due = due
        # request g goes to child g % G as its local request g // G
        sure = n if self.open else min(
            n, int(float(tr["min_qps"]) * self.seconds))
        keep = set(rng.choice(sure, min(int(tr["check_sample"]), sure),
                              replace=False).tolist())
        self.keep = sorted(keep)
        ctx = multiprocessing.get_context("spawn")
        for c in range(G):
            mine = range(c, n, G)
            spec = {"connections": int(tr["connections"]), "num": num,
                    "bodies": [json.dumps(
                        {"user": f"u{int(self.req_users[g])}",
                         "num": num}).encode() for g in mine],
                    "keep": [g // G for g in mine if g in keep]}
            if self.open:
                spec["due"] = [float(due[g]) for g in mine]
            here, there = ctx.Pipe()
            proc = ctx.Process(target=loadgen.child_main,
                               args=(there, spec), daemon=True)
            proc.start()
            there.close()
            self.children.append((proc, here))

    # -- set-up: everything the system does before the window -------------
    def setup(self) -> None:
        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.models.als import (
            ALSModel, ALSParams, recommend_batch)

        with self.stamps.stage("id_maps"):
            user_ids = BiMap(dict(zip(self.user_keys,
                                      range(self.n_users))))
            item_ids = BiMap(dict(zip(self.item_keys,
                                      range(self.n_items))))
        # the key lists are the benchmark's raw material, not the system's
        del self.user_keys, self.item_keys
        model = ALSModel(user_factors=self.U, item_factors=self.V,
                         n_users=self.n_users, n_items=self.n_items,
                         user_ids=user_ids, item_ids=item_ids,
                         params=ALSParams(rank=self.rank))
        with self.stamps.stage("bind"):
            self.qs, self.srv = _boot(model, self.config["server"],
                                      self.rank)
        num = int(self.traffic["num"])
        with self.stamps.stage("warm"):
            bound = self.qs.models[0]
            for b in self.traffic["warm_batches"]:
                recommend_batch(bound, np.zeros(int(b), dtype=np.int64),
                                num)
            conn = http.client.HTTPConnection("127.0.0.1", self.srv.port,
                                              timeout=120)
            for u in self.req_users[:8]:
                loadgen.post(conn, json.dumps(
                    {"user": f"u{int(u)}", "num": num}).encode(), num)
            conn.close()
            self.qs.recompile_sentinel.arm()
        self.bound = bound
        for _, pipe in self.children:
            pipe.send(self.srv.port)
        for _, pipe in self.children:
            if not pipe.poll(60) or pipe.recv() != "ready":
                raise RuntimeError("a load generator did not connect")

    # -- the timed window ---------------------------------------------------
    def window(self) -> None:
        T = self.seconds
        before = self.qs.metrics.export()
        t0 = time.monotonic() + 0.25
        for _, pipe in self.children:
            pipe.send((t0, T))
        clean_until = T
        spans = {}
        if self.traced:
            plan = self.traffic["trace"]
            clean_until = max(T - float(plan["before_end_s"]), 0.4 * T)
            time.sleep(max(t0 + clean_until - time.monotonic(), 0))
            after = self.qs.metrics.export()
            a0 = time.monotonic() - t0
            with trace.Capture(python=False) as cap:
                time.sleep(float(plan["device_s"]))
            spans["python_off"] = (a0, a0 + cap.window_s)
            on_chip = self.devs[0].platform != "cpu"
            if on_chip:  # a rehearsal's CPU trace has no device plane
                self.facts["trace"] = trace.reduce_device(cap.events,
                                                          cap.window_s)
                ops = trace.top_device_ops(self.facts["trace"])
            b0 = time.monotonic() - t0
            with trace.Capture(python=True) as cap:
                time.sleep(float(plan["host_s"]))
            spans["python_on"] = (b0, b0 + cap.window_s)
            if on_chip:
                self.facts["breakdown"] = {
                    "device_ops": ops,
                    "idle_gaps": trace.idle_gaps(cap.events)}
        time.sleep(max(t0 + T - time.monotonic(), 0))
        if not self.traced:
            after = self.qs.metrics.export()
        results = []
        for proc, pipe in self.children:
            if not pipe.poll(T + 120):
                raise RuntimeError("a load generator did not report")
            results.append(pipe.recv())
            proc.join(30)
        self.compiles = int(self.qs.recompile_sentinel.since_armed)
        self.facts["registry"] = (before, after)
        self._digest(results, clean_until, spans)

    def _digest(self, results, clean_until: float, spans: dict) -> None:
        G, T = len(results), self.seconds
        lat, late, done, sched = [], [], [], []
        self.answers = {}
        errors = []
        for c, r in enumerate(results):
            errors += r["errors"]
            for k, v in enumerate(r["lat"]):
                if v is None:
                    continue  # a closed loop sends only what it gets to
                lat.append(v)
                late.append(r["late"][k])
                done.append(r["done"][k])
                sched.append(self.due[k * G + c] if self.open
                             else r["done"][k] - max(v, 0.0))
            for k, payload in r["kept"].items():
                self.answers[int(k) * G + c] = payload
        lat, late = np.array(lat), np.array(late)
        done, sched = np.array(done), np.array(sched)
        ok = lat >= 0
        self.attempted, self.failed = len(lat), int((~ok).sum())
        if errors:
            self.say("request_errors", errors[:5])
        # a failed or wrong answer counts as missing: it never arrives
        lat_all = np.where(ok, lat, np.inf) * 1e3
        self.measured = {
            "query_p50_ms": _percentile(lat_all, 50),
            "query_p95_ms": _percentile(lat_all, 95),
            "served_qps": float((ok & (done >= 0) & (done <= T)).sum()
                                / T)}
        clean = sched < clean_until
        self.facts["loadgen"] = {
            "lat_p50_ms": _percentile(lat_all[clean], 50),
            "lat_p95_ms": _percentile(lat_all[clean], 95),
            "lat_p99_ms": _percentile(lat_all[clean], 99),
            "late_p95_ms": _percentile(late[clean] * 1e3, 95),
            "requests": int(clean.sum())}

        def qps(lo, hi):
            return float((ok & (done >= lo) & (done < hi)).sum()
                         / max(hi - lo, 1e-9))

        self.rates = {"untraced_part_qps": qps(0.0, clean_until),
                      **{f"traced_part_{k}_qps": qps(*v)
                         for k, v in spans.items()}}
        if self.open:  # is a backlog growing at the window's end?
            first, last = sched < 0.25 * T, sched >= 0.75 * T
            self.sweep_rows = {
                "answered_share": float(ok.mean()),
                "p50_first_quarter_ms": _percentile(lat_all[first], 50),
                "p50_last_quarter_ms": _percentile(lat_all[last], 50),
                "p95_last_quarter_ms": _percentile(lat_all[last], 95),
                "p99_ms": _percentile(lat_all, 99),
                "last_done_s": float(done.max()) if len(done) else 0.0}
            self.say("backlog", self.sweep_rows)
        self.say("window", {
            "attempted": self.attempted, "failed": self.failed,
            "compiles_since_warm": self.compiles, **self.measured,
            "gen_late_p95_ms": _percentile(late * 1e3, 95),
            "done_before_window": int((done < 0).sum()),
            "done_per_second": np.bincount(
                done[ok & (done >= 0)].astype(int),
                minlength=int(T))[:int(T)].tolist(),
            "latency_samples": int(ok.sum())})
        occ = readers.read(self.facts, {
            "reader": "registry", "metric": "pio_batch_occupancy",
            "stat": "mean"})
        self.facts["shapes"] = {"topk_dispatch": {
            "item_table_bytes": int(self.bound.item_factors.nbytes),
            "n_items": self.n_items, "rank": self.rank,
            "batch": float(occ or 1.0), "k": int(self.traffic["num"])}}

    def end_to_end(self) -> dict:
        return self.measured

    def traced_rates(self) -> dict:
        return self.rates

    # -- the output check, outside every clock ------------------------------
    def check(self) -> list:
        import jax.numpy as jnp

        lim = self.config["check"]

        scores = reference.topk_scores_fn()
        reqs = sorted(self.answers)
        score_gaps, rank_gaps, malformed = [], [], 0
        k = int(self.traffic["num"])
        parsed = {}
        for g in reqs:
            try:
                got = json.loads(self.answers[g])["itemScores"]
                ids = np.array([int(x["item"][1:]) for x in got])
                vals = np.array([float(x["score"]) for x in got])
                if len(ids) != k or len(set(ids.tolist())) != k \
                        or ids.min() < 0 or ids.max() >= self.n_items:
                    raise ValueError("ids repeated or out of range")
                parsed[g] = (ids, vals)
            except (KeyError, ValueError, TypeError):
                malformed += 1
        good = sorted(parsed)
        for s in range(0, len(good), 16):
            blk = good[s:s + 16]
            pad = blk + [blk[-1]] * (16 - len(blk))  # one compiled shape
            at, mag, best = (np.asarray(x, dtype=np.float64) for x in scores(
                self.U, self.V,
                jnp.asarray([int(self.req_users[g]) for g in pad]),
                jnp.asarray(np.stack([parsed[g][0] for g in pad]))))
            for row, g in enumerate(blk):
                sg, rg = reference.topk_gaps(parsed[g][1], at[row],
                                             mag[row], best[row])
                score_gaps.append(sg)
                rank_gaps.append(rg)
        n = len(score_gaps)
        self.failed += malformed
        self.say("check_detail", {
            "answers_compared": n, "malformed": malformed,
            "score_gap_p50": _percentile(score_gaps, 50),
            "rank_gap_p50": _percentile(rank_gaps, 50)})
        worst = float("inf")
        return [
            {"name": "score_gap_max", "limit": lim["score_gap_max"],
             "value": max(score_gaps) if n else worst},
            {"name": "rank_gap_max", "limit": lim["rank_gap_max"],
             "value": max(rank_gaps) if n else worst},
            {"name": "answers_not_compared", "limit": 0,
             "value": len(self.keep) - n},
            {"name": "failed_requests", "limit": 0, "value": self.failed},
            {"name": "compiles_in_window", "limit": 0,
             "value": self.compiles},
        ]

    def close(self) -> None:
        for proc, pipe in self.children:
            if proc.is_alive():
                proc.terminate()
            proc.join(10)
            pipe.close()
        if self.srv is not None:
            self.srv.shutdown()
        if self.qs is not None:
            self.qs.close()
