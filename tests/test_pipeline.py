"""Staged serving-pipeline tests (ISSUE 9): burst-load slot integrity,
deadline shedding, promote/reload mid-flight binding consistency, the
overlap/phase telemetry, and the OverlapTracker itself."""

import threading
import time
from datetime import datetime, timezone

import numpy as np
import pytest
from conftest import serve_staged_batch

from predictionio_tpu.controller import Context
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.data.storage.base import (
    STATUS_COMPLETED,
    EngineInstance,
)
from predictionio_tpu.models.als import ALSModel, ALSParams
from predictionio_tpu.obs import OverlapTracker
from predictionio_tpu.server.engineserver import (
    HTTPError,
    QueryServer,
    ServerConfig,
    StagedPipeline,
)
from predictionio_tpu.templates.recommendation import (
    default_engine_params,
    recommendation_engine,
)


def _model(nu=64, ni=40, rank=8, seed=0):
    rng = np.random.default_rng(seed)
    return ALSModel(
        user_factors=rng.standard_normal((nu, rank)).astype(np.float32),
        item_factors=rng.standard_normal((ni, rank)).astype(np.float32),
        n_users=nu, n_items=ni,
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
        params=ALSParams(rank=rank))


def _mk_server(cfg, model=None, persist=False):
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "pipe"))
    ctx = Context(app_name="pipe", _storage=storage)
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="p0", status=STATUS_COMPLETED, start_time=now,
        end_time=now, engine_id="pipe", engine_version="1",
        engine_variant="engine.json", engine_factory="synthetic")
    storage.engine_instances().insert(inst)
    model = model or _model()
    if persist:
        # make the instance reload()-able: persist the model blob the
        # way run_train does
        from predictionio_tpu.data.storage.base import Model
        from predictionio_tpu.workflow import persistence

        engine = recommendation_engine()
        ep = default_engine_params("pipe", rank=8)
        algo = engine.make_algorithms(ep)[0]
        stored = [algo.make_persistent_model(model, inst.id, 0)]
        storage.models().insert(Model(
            id=inst.id, models=persistence.dumps_models(stored)))
    qs = QueryServer(ctx, recommendation_engine(),
                     default_engine_params("pipe", rank=8),
                     [model], inst, cfg)
    return qs


def _items(result) -> list:
    return [s["item"] for s in result["itemScores"]]


def _assert_same_answer(got, want):
    """Same ranking; scores to float tolerance — different batch
    shapes legitimately differ by an ulp in reduction order."""
    assert _items(got) == _items(want)
    for g, w in zip(got["itemScores"], want["itemScores"]):
        assert g["score"] == pytest.approx(w["score"], rel=1e-5)


class TestBurstIntegrity:
    def test_flood_4x_max_batch_no_lost_or_swapped_slots(self):
        """4× max_batch concurrent submits: every caller gets exactly
        ITS user's result (slot swaps would cross users), nothing is
        lost, and nothing is duplicated into the wrong slot."""
        qs = _mk_server(ServerConfig(batching=True, max_batch=8,
                                     batch_window_ms=5.0,
                                     warm_start=False))
        assert isinstance(qs.batcher, StagedPipeline)
        want = {u: qs.query({"user": f"u{u}", "num": 3})
                for u in range(8)}
        n = 4 * 8
        users = [i % 8 for i in range(n)]
        results = [None] * n

        def fire(i):
            results[i] = qs.batcher.submit(
                {"user": f"u{users[i]}", "num": 3})

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, r in enumerate(results):
            assert not isinstance(r, HTTPError), f"slot {i}: {r}"
            _assert_same_answer(r, want[users[i]])
        # every query was counted exactly once
        assert qs.request_count >= n

    def test_burst_batches_actually_coalesce(self):
        """The occupancy histogram must show real coalescing under
        burst (the staged path must not shred into batch-1 slivers)."""
        qs = _mk_server(ServerConfig(batching=True, max_batch=16,
                                     batch_window_ms=20.0,
                                     warm_start=False))
        n = 48
        threads = [threading.Thread(
            target=lambda i=i: qs.batcher.submit(
                {"user": f"u{i % 8}", "num": 3})) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        occ = qs.metrics.snapshot()["pio_batch_occupancy"]
        assert occ["sum"] == n
        assert occ["max"] > 1  # at least one real coalesced batch

    def test_parse_errors_complete_without_device_round_trip(self):
        qs = _mk_server(ServerConfig(batching=True, max_batch=8,
                                     warm_start=False))
        r = qs.batcher.submit({"bogus": 1})
        assert isinstance(r, HTTPError) and r.status == 400
        r2 = qs.batcher.submit({"user": "u1", "num": 2})
        assert len(r2["itemScores"]) == 2


class TestDeadline:
    def _wedge(self, qs, seconds):
        """Wedge the pipeline: supplement blocks (assemble stage)."""
        class Wedged:
            def __init__(self, inner):
                self.inner = inner

            def supplement(self, q):
                time.sleep(seconds)
                return self.inner.supplement(q)

            def serve(self, q, ps):
                return self.inner.serve(q, ps)

        qs.serving = Wedged(qs.serving)

    def test_wedged_dispatch_sheds_503(self):
        qs = _mk_server(ServerConfig(batching=True, max_batch=4,
                                     queue_deadline_ms=150.0,
                                     warm_start=False))
        self._wedge(qs, 2.0)
        t0 = time.monotonic()
        r = qs.batcher.submit({"user": "u1", "num": 2})
        waited = time.monotonic() - t0
        assert isinstance(r, HTTPError) and r.status == 503
        assert waited < 1.5  # returned at the deadline, not after the
        # wedge cleared
        assert qs._deadline_exceeded.labels().value >= 1
        # the shed is visible as a 503 in the error series too
        assert qs._query_errors.labels(status="503").value >= 1

    def test_expired_queue_entries_never_dispatch(self):
        """Entries whose submitter already gave up are completed as
        corpses at pickup — the batch they would have joined must not
        contain them (no device work for dead callers)."""
        qs = _mk_server(ServerConfig(batching=True, max_batch=8,
                                     queue_deadline_ms=100.0,
                                     warm_start=False))
        self._wedge(qs, 0.8)
        n = 12
        results = [None] * n
        threads = [threading.Thread(
            target=lambda i=i: results.__setitem__(
                i, qs.batcher.submit({"user": "u1", "num": 2})))
            for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(isinstance(r, HTTPError) and r.status == 503
                   for r in results)
        assert qs._deadline_exceeded.labels().value == n
        # the wedge clears; the pipeline is healthy again
        time.sleep(1.0)
        qs.serving = qs.serving.inner
        assert len(qs.batcher.submit(
            {"user": "u2", "num": 2})["itemScores"]) == 2

    def test_deadline_zero_disables(self):
        qs = _mk_server(ServerConfig(batching=True,
                                     queue_deadline_ms=0.0,
                                     warm_start=False))
        r = qs.batcher.submit({"user": "u1", "num": 2})
        assert len(r["itemScores"]) == 2
        assert qs._deadline_exceeded.labels().value == 0


class TestMidFlightRebind:
    def test_promote_reload_storm_never_serves_torn_binding(self):
        """Queries flood the staged pipeline while reload() rebinds in
        a loop. Every response must be a complete, well-formed result
        from SOME binding — never a 500 from a half-swapped one
        (extends the PR 3 warm-race stress to the staged path)."""
        qs = _mk_server(ServerConfig(batching=True, max_batch=8,
                                     batch_window_ms=2.0,
                                     warm_start=False), persist=True)
        want = qs.query({"user": "u3", "num": 4})
        stop = threading.Event()
        rebind_errors = []

        def rebinder():
            while not stop.is_set():
                try:
                    qs.reload()
                except Exception as e:  # noqa: BLE001 — surface
                    rebind_errors.append(e)

        errors = []
        results = []
        lock = threading.Lock()

        def fire():
            for _ in range(20):
                r = qs.batcher.submit({"user": "u3", "num": 4})
                with lock:
                    if isinstance(r, HTTPError):
                        errors.append(r)
                    else:
                        results.append(r)

        rb = threading.Thread(target=rebinder)
        workers = [threading.Thread(target=fire) for _ in range(6)]
        rb.start()
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        stop.set()
        rb.join()
        assert not rebind_errors
        assert not errors, f"mid-rebind queries failed: {errors[:3]}"
        # same instance re-loaded → identical answers throughout
        for r in results:
            _assert_same_answer(r, want)

    def test_batch_binding_snapshot_is_consistent(self):
        """The assemble-time snapshot must ride the whole batch: a
        rebind between assemble and dispatch must not mix models."""
        qs = _mk_server(ServerConfig(batching=True, max_batch=4,
                                     warm_start=False), persist=True)
        ab = qs.batcher._assemble([
            type("E", (), {"query_json": {"user": "u1", "num": 2},
                           "t_enq": time.monotonic(), "obs": None,
                           "done": threading.Event(),
                           "slot": [None], "abandoned": False,
                           "deadline": None})()])
        assert ab.algorithms is not None
        assert ab.instance_id == qs.instance.id
        # the snapshot is by-reference frozen: a rebind swaps the
        # server's lists, not the batch's
        old_models = ab.models
        qs.reload()
        assert ab.models is old_models


class TestPipelineTelemetry:
    def test_phase_and_stage_series_recorded(self):
        qs = _mk_server(ServerConfig(batching=True, max_batch=8,
                                     warm_start=False))
        threads = [threading.Thread(
            target=lambda i=i: qs.batcher.submit(
                {"user": f"u{i % 8}", "num": 3})) for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = qs.metrics.snapshot()
        stages = snap["pio_pipeline_stage_seconds"]
        for stage in ("assemble", "dispatch", "readback"):
            assert f'stage={stage}' in stages
        phases = snap["pio_query_phase_seconds"]
        assert "phase=device_wait" in phases
        assert "phase=queue_wait" in phases
        status = qs.pipeline_status()
        assert status["mode"] == "staged"
        assert 0.0 <= status["overlap"]["deviceIdleFraction"] <= 1.0
        assert status["deadlineExceeded"] == 0

    def test_readback_phase_is_max_not_sum(self):
        """Satellite: the batch readback phase reports the worst
        query's serialization, not the sum over the batch."""
        qs = _mk_server(ServerConfig(batching=True, max_batch=8,
                                     warm_start=False))
        obs_list = [{} for _ in range(6)]
        serve_staged_batch(
            qs, [{"user": f"u{i}", "num": 3} for i in range(6)],
            obs_list=obs_list)
        per_query = [o["readbackMs"] for o in obs_list]
        batch_ms = obs_list[0]["readbackMs"]
        # identical batch value broadcast to every query's obs
        assert all(o.get("readbackMs") is not None for o in obs_list)
        # the recorded batch phase equals the max, and is NOT the sum
        phases = qs.metrics.snapshot()["pio_query_phase_seconds"]
        readback_ms = phases["phase=readback"]["max"] * 1000
        assert readback_ms <= sum(per_query) + 1e-6
        assert readback_ms >= max(per_query) * 0.5 - 1e-6

    @staticmethod
    def _supplemented(qs):
        """``pio_pipeline_supplement_batches_total`` by ``way``."""
        fam = qs.metrics.export()["pio_pipeline_supplement_batches_total"]
        return {c["labels"]["way"]: c["value"] for c in fam["children"]}

    def test_default_serving_batch_is_supplemented_by_identity(
            self, monkeypatch):
        """ISSUE 46: a batch of several queries whose serving inherits
        ``Serving.supplement`` counts once under ``identity``, records
        phase ``supplement`` once and starts no pool thread."""
        from predictionio_tpu.workflow import batch_predict as bp

        # as a fresh process has it: no executor made
        monkeypatch.setattr(bp, "_dispatch_pool", None)
        qs = _mk_server(ServerConfig(batching=True, max_batch=8,
                                     warm_start=False))
        assert self._supplemented(qs) == {
            "identity": 0, "serial": 0, "pool": 0}
        before = set(threading.enumerate())
        slots, _ = serve_staged_batch(
            qs, [{"user": f"u{i}", "num": 3} for i in range(6)])
        assert all(len(_items(r)) == 3 for r in slots)
        assert self._supplemented(qs) == {
            "identity": 1, "serial": 0, "pool": 0}
        phases = qs.metrics.snapshot()["pio_query_phase_seconds"]
        assert phases["phase=supplement"]["count"] == 1
        assert bp._dispatch_pool is None
        assert not [t.name for t in set(threading.enumerate()) - before
                    if t.name.startswith("algo-batch-dispatch")]

    @pytest.mark.parametrize("n,way", [(1, "serial"), (4, "pool")])
    def test_overriding_serving_batch_counts_by_its_size(self, n, way):
        """The wedge's duck-typed serving keeps the behaviour it had:
        one query on the assemble thread, more on the shared pool."""
        qs = _mk_server(ServerConfig(batching=True, max_batch=8,
                                     warm_start=False))
        TestDeadline()._wedge(qs, 0.0)
        slots, _ = serve_staged_batch(
            qs, [{"user": f"u{i}", "num": 2} for i in range(n)])
        assert all(len(_items(r)) == 2 for r in slots)
        want = {"identity": 0, "serial": 0, "pool": 0, way: 1}
        assert self._supplemented(qs) == want
        phases = qs.metrics.snapshot()["pio_query_phase_seconds"]
        assert phases["phase=supplement"]["count"] == 1


class TestInflightDepth:
    """PR 26: ``pipeline_depth`` 0 resolves to 2 on every backend, and
    the pipeline never holds more than ``depth`` unserved batches."""

    def test_auto_depth_is_two_whatever_the_backend(self, monkeypatch):
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        qs = _mk_server(ServerConfig(batching=True, warm_start=False))
        assert qs.batcher.depth == 2
        assert qs.pipeline_status()["depth"] == 2

    @pytest.mark.parametrize("configured,depth", [(0, 2), (3, 3)])
    def test_holds_depth_batches_and_close_drains_a_parked_assemble(
            self, configured, depth):
        """Resolvers block on an Event: ``depth`` batches are launched
        and no more, and ``close`` drains with the assemble thread
        parked on the in-flight semaphore."""
        qs = _mk_server(ServerConfig(batching=True, max_batch=1,
                                     batch_window_ms=0.0,
                                     pipeline_depth=configured,
                                     warm_start=False))
        pipe = qs.batcher
        assert qs.pipeline_status()["depth"] == depth
        gate = threading.Event()
        algo = qs.algorithms[0]
        inner = algo.batch_predict_async
        launched = []

        def gated(model, supplemented):
            resolve = inner(model, supplemented)
            launched.append(gate.is_set())

            def blocked():
                gate.wait(30)
                return resolve()
            return blocked

        algo.batch_predict_async = gated
        n = depth + 4
        results = [None] * n
        callers = [threading.Thread(
            target=lambda i=i: results.__setitem__(
                i, pipe.submit({"user": f"u{i}", "num": 3})))
            for i in range(n)]
        for t in callers:
            t.start()
        deadline = time.monotonic() + 10
        while len(launched) < depth and time.monotonic() < deadline:
            time.sleep(0.005)
        closer = threading.Thread(target=pipe.close)
        closer.start()  # assemble is parked on the semaphore
        closer.join(0.1)
        assert closer.is_alive()
        assert len(launched) == depth
        assert qs.overlap.active("device") == depth
        gate.set()
        for t in callers + [closer]:
            t.join(10)
            assert not t.is_alive()
        assert not any(t.is_alive() for t in pipe._threads)
        # every launch before the gate opened was one of the first
        # ``depth``; the rest waited for a slot
        assert launched.count(False) == depth and len(launched) == n
        for r in results:
            assert len(r["itemScores"]) == 3
        with pytest.raises(ValueError):
            pipe._inflight.release()  # every slot was given back


class TestOverlapTracker:
    def test_overlap_accounting(self):
        t = [0.0]
        tr = OverlapTracker(time_fn=lambda: t[0])
        tr.enter("device")          # t=0
        t[0] = 1.0
        assert tr.enter("assemble") == 0  # host joins at t=1
        t[0] = 3.0
        tr.exit("assemble")         # overlap [1, 3] = 2s
        t[0] = 4.0
        tr.exit("device")           # device busy [0, 4]
        t[0] = 5.0
        snap = tr.snapshot()
        assert snap["wall_sec"] == pytest.approx(5.0)
        assert snap["device_busy_sec"] == pytest.approx(4.0)
        assert snap["overlap_sec"] == pytest.approx(2.0)
        assert snap["device_idle_fraction"] == pytest.approx(0.2)
        assert snap["overlap_fraction"] == pytest.approx(0.4)

    def test_enter_returns_prior_count(self):
        tr = OverlapTracker()
        assert tr.enter("device") == 0
        assert tr.enter("device") == 1  # overlapped launch
        tr.exit("device")
        tr.exit("device")
        assert tr.active("device") == 0

    def test_idle_without_traffic(self):
        tr = OverlapTracker()
        assert tr.device_idle_fraction() == 1.0
        assert tr.overlap_fraction() == 0.0
