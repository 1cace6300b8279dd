"""Progressive-delivery tests (ISSUE 3): release registry, traffic
splitter, health policy, the end-to-end canary lifecycle (erroring
candidate auto-rolls-back; healthy candidate ramps to 100% and becomes
the pinned stable), shadow mode, the release CLI, concurrent
per-algorithm dispatch, and the /reload warm-race stress test."""

import json
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest
from conftest import serve_staged_batch

from predictionio_tpu.cli import main as cli_main
from predictionio_tpu.controller import Context
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.data.storage.base import (
    STATUS_COMPLETED,
    EngineInstance,
)
from predictionio_tpu.rollout import (
    ArmWindow,
    HealthPolicy,
    ReleaseRegistry,
    TrafficSplitter,
    window_quantile,
)
from predictionio_tpu.server.engineserver import (
    QueryServer,
    ServerConfig,
    create_engine_server,
)
from predictionio_tpu.templates.recommendation import (
    default_engine_params,
    recommendation_engine,
)
from predictionio_tpu.workflow.core import load_models_for_deploy

T0 = datetime(2026, 1, 1, tzinfo=timezone.utc)


def call(port, method, path, body=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            raw = resp.read()
            ctype = resp.headers.get("Content-Type", "")
            return resp.status, (json.loads(raw) if "json" in ctype
                                 else raw.decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


# ---------------------------------------------------------------------------
# unit: registry
# ---------------------------------------------------------------------------

def _mem_storage_with_instance(iid="i1"):
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    now = datetime.now(timezone.utc)
    storage.engine_instances().insert(EngineInstance(
        id=iid, status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="e", engine_version="1", engine_variant="v",
        engine_factory="f"))
    return storage


class TestReleaseRegistry:
    def test_deploy_pin_promote_rollback_history(self):
        storage = _mem_storage_with_instance("i1")
        now = datetime.now(timezone.utc)
        storage.engine_instances().insert(EngineInstance(
            id="i2", status=STATUS_COMPLETED, start_time=now,
            end_time=now, engine_id="e", engine_version="1",
            engine_variant="v", engine_factory="f"))
        reg = ReleaseRegistry(storage, "e", "1", "v")
        reg.record_deploy("i1", actor="test", reason="first")
        assert reg.state()["stable"] == "i1"
        reg.pin("i1", actor="test")
        assert reg.pinned_instance() == "i1"
        reg.start_candidate("i2", 0.05, mode="canary", actor="gate")
        st = reg.state()
        assert st["candidate"] == "i2" and st["fraction"] == 0.05
        reg.set_fraction(0.25, actor="gate")
        assert reg.state()["fraction"] == 0.25
        reg.promote("i2", actor="gate", reason="healthy")
        st = reg.state()
        assert st["stable"] == "i2" and st["pinned"] == "i2"
        assert st["candidate"] == "" and st["previousStable"] == "i1"
        # stable rollback (no candidate): reverts to previous stable
        reg.rollback(actor="op", reason="bad promote")
        st = reg.state()
        assert st["stable"] == "i1" and st["pinned"] == "i1"
        actions = [e.action for e in reg.history()]
        assert actions == ["deploy", "pin", "canary", "ramp",
                           "promote", "rollback"]
        # persisted: a fresh registry over the same storage reads it all
        again = ReleaseRegistry(storage, "e", "1", "v")
        assert [e.action for e in again.history()] == actions
        assert ("e", "1", "v") in ReleaseRegistry.list_tracked(storage)

    def test_candidate_rollback_and_guards(self):
        storage = _mem_storage_with_instance("i1")
        reg = ReleaseRegistry(storage, "e", "1", "v")
        with pytest.raises(ValueError):
            reg.pin("nope")  # unknown instance
        with pytest.raises(ValueError):
            reg.rollback()  # nothing to roll back
        reg.start_candidate("i1", 0.01, actor="t")
        ev = reg.rollback(actor="gate", reason="error rate")
        assert ev.extra["kind"] == "candidate"
        assert reg.state()["candidate"] == ""

    def test_unpin(self):
        storage = _mem_storage_with_instance("i1")
        reg = ReleaseRegistry(storage, "e", "1", "v")
        reg.pin("i1")
        reg.unpin(actor="t")
        assert reg.pinned_instance() is None


# ---------------------------------------------------------------------------
# unit: splitter + policy
# ---------------------------------------------------------------------------

class TestSplitter:
    def test_deterministic_and_monotone(self):
        lo = TrafficSplitter(0.1)
        hi = TrafficSplitter(0.5)
        queries = [{"user": f"u{i}"} for i in range(2000)]
        picks = [lo.routes_candidate(q) for q in queries]
        assert picks == [lo.routes_candidate(q) for q in queries]
        share = sum(picks) / len(picks)
        assert 0.06 < share < 0.14  # ~10% of cohort space
        # ramping only ADDS cohort, never churns users between arms
        assert all(hi.routes_candidate(q)
                   for q, p in zip(queries, picks) if p)

    def test_edges_and_fallback_key(self):
        s = TrafficSplitter(0.0)
        assert not s.routes_candidate({"user": "u1"})
        s.set_fraction(1.0)
        assert s.routes_candidate({"user": "u1"})
        # entity-less queries still split deterministically
        assert (s.cohort_key({"num": 3})
                == s.cohort_key({"num": 3}))
        assert s.route({"user": "u1"}) == "candidate"
        s.shadow = True
        assert s.route({"user": "u1"}) == "stable"


class TestPolicy:
    def test_verdicts(self):
        p = HealthPolicy(min_queries=10, max_error_rate=0.1,
                         error_rate_slack=0.05, p99_regression=2.0)
        ok = ArmWindow(queries=100, errors=1, p99=0.010)
        assert p.evaluate(ok, ArmWindow(3, 0, None)).action == "hold"
        assert p.evaluate(
            ok, ArmWindow(50, 20, 0.01)).action == "rollback"
        # relative gate: stable erroring too, candidate within slack
        noisy = ArmWindow(queries=100, errors=8, p99=0.010)
        assert p.evaluate(
            noisy, ArmWindow(50, 4, 0.01)).action == "advance"
        # p99 regression
        assert p.evaluate(
            ok, ArmWindow(50, 0, 0.05)).action == "rollback"
        assert p.evaluate(
            ok, ArmWindow(50, 0, 0.012)).action == "advance"

    def test_ramp_schedule(self):
        p = HealthPolicy()
        assert p.next_fraction(0.01) == 0.05
        assert p.next_fraction(0.25) == 1.0
        assert p.next_fraction(1.0) is None

    def test_window_quantile(self):
        from predictionio_tpu.obs import StreamingHistogram

        h = StreamingHistogram(bounds=[0.01, 0.1, 1.0])
        for _ in range(100):
            h.observe(0.005)  # old traffic: fast
        start = h.bucket_counts()
        for _ in range(50):
            h.observe(0.5)    # window traffic: slow
        q = window_quantile(start, h.bucket_counts(), 0.99)
        assert 0.1 < q <= 1.0  # sees ONLY the window's slow samples
        assert window_quantile(start, start, 0.99) is None


# ---------------------------------------------------------------------------
# E2E: the full canary lifecycle over a real trained engine
# ---------------------------------------------------------------------------

def _synth_als_model(seed: int, n_users: int = 24, n_items: int = 24,
                     rank: int = 4):
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.als import ALSModel, ALSParams

    rng = np.random.default_rng(seed)
    return ALSModel(
        user_factors=rng.standard_normal(
            (n_users, rank)).astype(np.float32),
        item_factors=rng.standard_normal(
            (n_items, rank)).astype(np.float32),
        n_users=n_users, n_items=n_items,
        user_ids=BiMap({f"u{i}": i for i in range(n_users)}),
        item_ids=BiMap({f"i{i}": i for i in range(n_items)}),
        params=ALSParams(rank=rank))


@pytest.fixture(scope="module")
def two_releases():
    """Two COMPLETED instances of the same engine triple with
    persisted model blobs — the post-train state `deploy`/`reload`/
    `start_canary` load from, synthesized without the training path."""
    from predictionio_tpu.data.storage.base import Model
    from predictionio_tpu.workflow import persistence

    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "relapp"))
    ctx = Context(app_name="relapp", _storage=storage)
    engine = recommendation_engine()
    ep = default_engine_params("relapp", rank=4)
    ids = []
    for n, seed in (("rl1", 1), ("rl2", 2)):
        start = T0 + timedelta(minutes=len(ids))
        storage.engine_instances().insert(EngineInstance(
            id=n, status=STATUS_COMPLETED, start_time=start,
            end_time=start, engine_id="rel", engine_version="1",
            engine_variant="engine.json", engine_factory="synthetic"))
        storage.models().insert(Model(
            id=n,
            models=persistence.dumps_models([_synth_als_model(seed)])))
        ids.append(n)
    return ctx, engine, ep, ids[0], ids[1]


def _serve(two_releases, iid, config=None):
    ctx, engine, ep, _, _ = two_releases
    inst = ctx.storage.engine_instances().get(iid)
    models = load_models_for_deploy(ctx, engine, inst, ep)
    qs = QueryServer(ctx, engine, ep, models, inst,
                     config or ServerConfig(warm_start=False))
    srv = create_engine_server(qs, "127.0.0.1", 0).start_background()
    return qs, srv


class PoisonServing:
    """Candidate serving that always fails — the 'bad retrain'."""

    def supplement(self, q):
        raise RuntimeError("candidate poison")

    def serve(self, q, ps):  # pragma: no cover — supplement raises
        raise RuntimeError("candidate poison")


def _drive_until(port, qs, pred, timeout=30.0, n_users=20):
    """Fire query traffic until ``pred()`` or timeout; returns the
    collected (status, body) pairs."""
    results = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not pred():
        for u in range(n_users):
            results.append(call(port, "POST", "/queries.json",
                                {"user": f"u{u}", "num": 2}))
        time.sleep(0.02)
    return results


class TestCanaryLifecycle:
    def test_erroring_candidate_auto_rolls_back(self, two_releases):
        ctx, engine, ep, iid1, iid2 = two_releases
        qs, srv = _serve(two_releases, iid1)
        try:
            policy = HealthPolicy(window_sec=0.2, min_queries=5,
                                  ramp=(0.5, 1.0),
                                  max_error_rate=0.2)
            ctl = qs.start_canary(iid2, fraction=0.5, policy=policy,
                                  actor="test", reason="bad retrain")
            assert qs._candidate is not None
            qs._candidate.serving = PoisonServing()  # the bad model

            results = _drive_until(
                srv.port, qs, lambda: not ctl.active)
            assert not ctl.active, \
                "controller did not conclude within the timeout"
            assert ctl.outcome == "rolled_back"
            assert qs._candidate is None
            assert qs.instance.id == iid1  # stable untouched

            # canary blast radius: SOME queries saw candidate 500s
            # while it was live, but stable answers stayed correct
            # and post-rollback everything is 200 again
            assert any(status == 500 for status, _ in results)
            ok = [b for status, b in results if status == 200]
            assert ok and all(b.get("itemScores") for b in ok)
            status, body = call(srv.port, "POST", "/queries.json",
                                {"user": "u1", "num": 2})
            assert status == 200 and body["itemScores"]

            # the registry recorded the full story
            status, rel = call(srv.port, "GET", "/release.json")
            assert status == 200
            actions = [e["action"] for e in rel["history"]]
            assert "canary" in actions and "rollback" in actions
            assert rel["rollout"]["outcome"] == "rolled_back"
            assert rel["serving"]["stableInstanceId"] == iid1
            assert rel["arms"]["candidate"]["errors"] > 0
        finally:
            srv.shutdown()

    def test_healthy_candidate_ramps_to_pinned_stable(
            self, two_releases):
        ctx, engine, ep, iid1, iid2 = two_releases
        qs, srv = _serve(two_releases, iid1)
        try:
            # p99_regression is effectively disabled: with a 3-query
            # minimum sample, one scheduler hiccup on a candidate
            # query under full-suite load flips the 2x default and
            # rolls back a healthy canary (observed flake). This test
            # exercises the ramp/promote mechanics; the latency gate
            # has its own coverage in TestPolicy.
            policy = HealthPolicy(window_sec=0.15, min_queries=3,
                                  ramp=(0.25, 1.0),
                                  p99_regression=1000.0)
            ctl = qs.start_canary(iid2, policy=policy, actor="test",
                                  reason="healthy retrain")
            assert ctl.splitter.fraction == 0.25  # first ramp step

            results = _drive_until(
                srv.port, qs, lambda: not ctl.active)
            assert not ctl.active, \
                "controller did not conclude within the timeout"
            assert ctl.outcome == "promoted"
            # zero failed queries across the entire ramp + promote swap
            assert all(status == 200 for status, _ in results)
            assert all(b.get("itemScores") for _, b in results)

            # the candidate IS the serving stable now, and pinned
            assert qs.instance.id == iid2
            st = qs.releases.state()
            assert st["stable"] == iid2 and st["pinned"] == iid2
            actions = [e.action for e in qs.releases.history()]
            assert "ramp" in actions and "promote" in actions
            status, body = call(srv.port, "GET", "/status.json")
            assert body["release"]["stable"] == iid2
            # reload now binds the pinned (promoted) release
            status, body = call(srv.port, "POST", "/reload")
            assert status == 200 and body["engineInstanceId"] == iid2
        finally:
            srv.shutdown()

    def test_shadow_mirrors_without_affecting_answers(
            self, two_releases):
        ctx, engine, ep, iid1, iid2 = two_releases
        qs, srv = _serve(two_releases, iid1)
        try:
            policy = HealthPolicy(window_sec=0.2, min_queries=3)
            ctl = qs.start_canary(iid2, shadow=True, policy=policy,
                                  actor="test")
            # even a POISONED shadow candidate never surfaces to users
            qs._candidate.serving = PoisonServing()
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline and ctl.windows < 2:
                for u in range(10):
                    status, body = call(
                        srv.port, "POST", "/queries.json",
                        {"user": f"u{u}", "num": 2})
                    assert status == 200 and body["itemScores"]
                time.sleep(0.02)
            assert ctl.windows >= 2, "gate windows did not evaluate"
            # shadow never auto-promotes or auto-rolls-back
            assert ctl.active and qs.instance.id == iid1
            # the mirrored candidate errors were counted
            q, e, _ = qs.release_arm_snapshot("candidate")
            assert e > 0
            # operator rollback ends it
            status, body = call(srv.port, "POST", "/release/rollback")
            assert status == 200
            assert not ctl.active and qs._candidate is None
        finally:
            srv.shutdown()

    def test_canary_http_route_and_guards(self, two_releases):
        ctx, engine, ep, iid1, iid2 = two_releases
        qs, srv = _serve(two_releases, iid1)
        try:
            # guards: unknown instance, stable-as-candidate
            status, _ = call(srv.port, "POST", "/release/canary",
                             {"instanceId": "nope"})
            assert status == 404
            status, _ = call(srv.port, "POST", "/release/canary",
                             {"instanceId": iid1})
            assert status == 400
            status, _ = call(srv.port, "POST", "/release/canary", {})
            assert status == 400
            # promote with nothing bound
            status, _ = call(srv.port, "POST", "/release/promote")
            assert status == 409
            # start over HTTP with an explicit fraction
            status, body = call(srv.port, "POST", "/release/canary",
                                {"instanceId": iid2, "fraction": 0.5,
                                 "reason": "via http"})
            assert status == 200
            assert body["rollout"]["fraction"] == 0.5
            # double-start is rejected while one is live
            status, _ = call(srv.port, "POST", "/release/canary",
                             {"instanceId": iid2})
            assert status == 409
            # operator promote skips the rest of the ramp
            status, body = call(srv.port, "POST", "/release/promote")
            assert status == 200 and body["engineInstanceId"] == iid2
            assert qs.instance.id == iid2
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# CLI: ptpu release / status / undeploy
# ---------------------------------------------------------------------------

class TestReleaseCLI:
    def test_list_show_pin(self, capsys):
        storage = _mem_storage_with_instance("i1")
        assert cli_main(["release", "list"], storage=storage) == 0
        assert "No releases" in capsys.readouterr().out
        rc = cli_main(["release", "pin", "i1", "--engine-id", "e",
                       "--engine-json", "v", "--reason", "known good"],
                      storage=storage)
        assert rc == 0
        assert cli_main(["release", "list"], storage=storage) == 0
        out = capsys.readouterr().out
        assert "e v1" in out and "pinned=i1" in out
        assert cli_main(["release", "show", "--engine-id", "e",
                         "--engine-json", "v"], storage=storage) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["state"]["pinned"] == "i1"
        assert payload["history"][-1]["reason"] == "known good"
        # pin guards: unknown instance
        rc = cli_main(["release", "pin", "nope", "--engine-id", "e",
                       "--engine-json", "v"], storage=storage)
        assert rc == 1
        # unpin
        rc = cli_main(["release", "pin", "--clear", "--engine-id", "e",
                       "--engine-json", "v"], storage=storage)
        assert rc == 0
        assert ReleaseRegistry(storage, "e", "1",
                               "v").pinned_instance() is None

    def test_status_reports_releases(self, capsys):
        storage = _mem_storage_with_instance("i1")
        ReleaseRegistry(storage, "e", "1", "v").record_deploy(
            "i1", actor="test")
        assert cli_main(["status"], storage=storage) == 0
        out = capsys.readouterr().out
        assert "Release [e v1]: stable=i1" in out

    def test_undeploy_records_history(self, two_releases, capsys):
        ctx, engine, ep, iid1, _ = two_releases
        qs, srv = _serve(two_releases, iid1)
        rc = cli_main(["undeploy", "--ip", "127.0.0.1",
                       "--port", str(srv.port)],
                      storage=ctx.storage)
        assert rc == 0
        out = capsys.readouterr().out
        assert iid1 in out
        events = ReleaseRegistry(
            ctx.storage, "rel", "1", "engine.json").history()
        undeploys = [e for e in events if e.action == "undeploy"]
        assert undeploys and undeploys[-1].instance_id == iid1

    def test_release_status_falls_back_to_storage(self, capsys):
        storage = _mem_storage_with_instance("i1")
        ReleaseRegistry(storage, "default", "1",
                        "engine.json").record_deploy("i1")
        rc = cli_main(["release", "status", "--port", "1"],
                      storage=storage)
        assert rc == 0
        captured = capsys.readouterr()
        assert "unreachable" in captured.err
        assert json.loads(captured.out)["state"]["stable"] == "i1"


# ---------------------------------------------------------------------------
# fake-engine scaffolding: parallel dispatch + reload warm race
# ---------------------------------------------------------------------------

@dataclass
class FQ:
    user: str = ""
    num: int = 1


class FakeModel:
    def __init__(self, tag):
        self.tag = tag
        self.algo_gen = None


class FakeAlgo:
    query_class = FQ

    def __init__(self, gen, predict_delay=0.0, warm_gate=None):
        self.gen = gen
        self.predict_delay = predict_delay
        self.warm_gate = warm_gate  # Event the test releases
        self.warm_runs = 0

    def bind_serving(self, ctx):
        pass

    def prepare_serving_model(self, model, max_batch):
        # stamp the pairing: a torn binding (this algo generation
        # serving another bind's model) is detected at predict time
        model.algo_gen = self.gen
        return model

    def warm_serving(self, model, max_batch):
        if self.warm_gate is not None:
            assert self.warm_gate.wait(timeout=30)
        self.warm_runs += 1

    def predict(self, model, query):
        if self.predict_delay:
            time.sleep(self.predict_delay)
        assert model.algo_gen == self.gen, \
            f"TORN BINDING: algo gen {self.gen} got model of gen " \
            f"{model.algo_gen}"
        return model.tag

    def batch_predict(self, model, queries):
        if self.predict_delay:
            time.sleep(self.predict_delay)
        return [model.tag] * len(queries)


class FakeServing:
    def supplement(self, q):
        return q

    def serve(self, q, predictions):
        return {"tags": list(predictions)}


class FakeEngine:
    def __init__(self, n_algos=1, predict_delay=0.0, gated_warm=False):
        self.n_algos = n_algos
        self.predict_delay = predict_delay
        self.gated_warm = gated_warm
        self.gen = 0
        self.gates = []  # one Event per bind generation
        self.made = []   # the algorithm list of each generation

    def make_algorithms(self, ep):
        self.gen += 1
        gate = threading.Event() if self.gated_warm else None
        self.gates.append(gate)
        algos = [FakeAlgo(self.gen, self.predict_delay, gate)
                 for _ in range(self.n_algos)]
        self.made.append(algos)
        return algos

    def make_serving(self, ep):
        return FakeServing()


def _fake_instance(storage, iid, engine_id="fk"):
    # start_time ordering makes the LAST-created instance the
    # "latest COMPLETED" reload target
    start = (datetime.now(timezone.utc)
             + timedelta(seconds=int(iid[-1])))
    inst = EngineInstance(
        id=iid, status=STATUS_COMPLETED, start_time=start,
        end_time=start, engine_id=engine_id, engine_version="1",
        engine_variant="engine.json", engine_factory="fake")
    storage.engine_instances().insert(inst)
    return inst


def _fake_ctx():
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "fkapp"))
    return Context(app_name="fkapp", _storage=storage)


class TestParallelAlgoDispatch:
    def test_independent_algorithms_dispatch_concurrently(self):
        """Satellite: the per-algorithm predict loop (the reference's
        CreateServer.scala 'TODO: Parallelize') runs concurrently —
        wall time of a 3-algorithm query is ~one delay, not three."""
        ctx = _fake_ctx()
        inst = _fake_instance(ctx.storage, "p1")
        engine = FakeEngine(n_algos=3, predict_delay=0.2)
        qs = QueryServer(ctx, engine, object(),
                         [FakeModel("a"), FakeModel("b"),
                          FakeModel("c")],
                         inst, ServerConfig(warm_start=False))
        t0 = time.monotonic()
        result = qs.query({"user": "u1"})
        wall = time.monotonic() - t0
        # order preserved (serving sees params order), and concurrent:
        # serial would be >= 0.6s
        assert result == {"tags": ["a", "b", "c"]}
        assert wall < 0.45, f"predictions look serial: {wall:.2f}s"

    def test_batched_dispatch_also_concurrent(self):
        """The staged pipeline / batch-predict lane shares the fix: one
        concurrent batch_predict dispatch per algorithm."""
        ctx = _fake_ctx()
        inst = _fake_instance(ctx.storage, "p2")
        engine = FakeEngine(n_algos=3, predict_delay=0.2)
        qs = QueryServer(ctx, engine, object(),
                         [FakeModel("a"), FakeModel("b"),
                          FakeModel("c")],
                         inst, ServerConfig(batching=True,
                                            warm_start=False))
        t0 = time.monotonic()
        out, _ = serve_staged_batch(qs, [{"user": "u1"}, {"user": "u2"}])
        wall = time.monotonic() - t0
        qs.close()
        assert [o["tags"] for o in out] == [["a", "b", "c"]] * 2
        assert wall < 0.45, f"batch dispatch looks serial: {wall:.2f}s"


class TestReloadWarmRace:
    """The documented warm race (engineserver.py ~:188-216): a stale
    deploy-time warm thread must never flip ``warm_done`` while a
    post-reload re-warm is still compiling, and concurrent queries
    during a reload must never observe a torn model binding."""

    def _boot(self, monkeypatch):
        ctx = _fake_ctx()
        inst1 = _fake_instance(ctx.storage, "w1")
        _fake_instance(ctx.storage, "w2")  # later start_time → latest
        engine = FakeEngine(gated_warm=True)

        def fake_load(ctx_, engine_, instance, ep):
            return [FakeModel(instance.id)]

        import predictionio_tpu.workflow.core as wfcore
        monkeypatch.setattr(wfcore, "load_models_for_deploy", fake_load)
        qs = QueryServer(ctx, engine, object(), [FakeModel("w1")],
                         inst1, ServerConfig(warm_start=True))
        return ctx, engine, qs

    def test_stale_warm_thread_never_reports_warm(self, monkeypatch):
        ctx, engine, qs = self._boot(monkeypatch)
        gate1 = engine.gates[0]  # deploy-time warm, still blocked
        assert not qs.warm_done.is_set()
        qs.reload()  # rebinds to w2, starts gen-2 re-warm
        gate2 = engine.gates[1]
        assert not qs.warm_done.is_set()
        # release the STALE deploy-time warm thread; it must NOT set
        # warm_done — the re-warm (gen 2) is still compiling
        gate1.set()
        stale_algo = engine.made[0][0]
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and stale_algo.warm_runs == 0:
            time.sleep(0.01)
        assert stale_algo.warm_runs == 1  # the stale thread finished
        time.sleep(0.1)  # give a buggy stale thread time to misfire
        assert not qs.warm_done.is_set(), \
            "stale warm thread flipped warm_done during re-warm"
        # releasing the re-warm completes the warmup for real
        gate2.set()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline \
                and not qs.warm_done.is_set():
            time.sleep(0.01)
        assert qs.warm_done.is_set()

    def test_concurrent_queries_never_see_torn_binding(
            self, monkeypatch):
        ctx, engine, qs = self._boot(monkeypatch)
        for gate in engine.gates:
            gate.set()
        stop = threading.Event()
        failures = []
        tags = set()

        def hammer():
            while not stop.is_set():
                try:
                    out = qs.query({"user": "u1"})
                    tags.add(out["tags"][0])
                except Exception as e:  # noqa: BLE001 — recorded
                    failures.append(repr(e))

        threads = [threading.Thread(target=hammer) for _ in range(6)]
        for t in threads:
            t.start()
        try:
            for _ in range(10):  # reload under fire, repeatedly
                qs.reload()
                engine.gates[-1].set()  # release each re-warm
                time.sleep(0.03)       # let queries land mid-swap
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert not failures, f"torn binding observed: {failures[:3]}"
        # queries saw only whole bindings: the models of w1 and w2
        assert tags <= {"w1", "w2"} and "w2" in tags
        # after the final reload every new query is the new release
        assert qs.query({"user": "u1"})["tags"] == ["w2"]
