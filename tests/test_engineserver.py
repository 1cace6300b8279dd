"""Engine server + batch predict tests: train → deploy → HTTP queries →
feedback/reload/stop, and the JSON-lines batch-predict flow."""

import json
import urllib.error
import urllib.request
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from predictionio_tpu.controller import Context
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.server.engineserver import ServerConfig, deploy
from predictionio_tpu.templates.recommendation import (
    default_engine_params,
    recommendation_engine,
)
from predictionio_tpu.workflow import run_train
from predictionio_tpu.workflow.batch_predict import run_batch_predict

T0 = datetime(2026, 1, 1, tzinfo=timezone.utc)


@pytest.fixture(scope="module")
def trained_ctx():
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    app_id = storage.apps().insert(App(0, "srvapp"))
    es = storage.events()
    es.init(app_id)
    rng = np.random.default_rng(7)
    events = []
    t = T0
    for u in range(20):
        items = rng.choice(20, size=6, replace=False)
        for i in items:
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": float(rng.integers(1, 6))}),
                event_time=t))
            t += timedelta(seconds=30)
    es.insert_batch(events, app_id)
    ctx = Context(app_name="srvapp", _storage=storage)
    engine = recommendation_engine()
    ep = default_engine_params("srvapp", rank=4, num_iterations=4, seed=3)
    run_train(ctx, engine, ep, engine_id="srv", engine_version="1")
    return ctx, engine, ep


def call(port, method, path, body=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req) as resp:
            raw = resp.read()
            ctype = resp.headers.get("Content-Type", "")
            return resp.status, (json.loads(raw) if "json" in ctype
                                 else raw.decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


@pytest.fixture()
def served(trained_ctx):
    ctx, engine, ep = trained_ctx
    srv = deploy(ctx, engine, ep, engine_id="srv", engine_version="1",
                 config=ServerConfig(feedback=True, feedback_app_name="srvapp"),
                 host="127.0.0.1", port=0)
    srv.start_background()
    yield ctx, srv
    srv.shutdown()


class TestEngineServer:
    def test_queries(self, served):
        ctx, srv = served
        status, body = call(srv.port, "POST", "/queries.json",
                            {"user": "u1", "num": 5})
        assert status == 200
        assert len(body["itemScores"]) == 5
        scores = [s["score"] for s in body["itemScores"]]
        assert scores == sorted(scores, reverse=True)

    def test_bad_query_400(self, served):
        ctx, srv = served
        status, _ = call(srv.port, "POST", "/queries.json",
                         {"nonsense": True})
        assert status == 400

    def test_status_page_and_json(self, served):
        ctx, srv = served
        call(srv.port, "POST", "/queries.json", {"user": "u1", "num": 3})
        status, html = call(srv.port, "GET", "/")
        assert status == 200 and "requests served" in html
        status, body = call(srv.port, "GET", "/status.json")
        assert status == 200 and body["requestCount"] >= 1
        assert body["engineId"] == "srv"

    def test_feedback_event_written(self, served):
        ctx, srv = served
        before = len(list(ctx.event_store.find("srvapp",
                                               event_names=["predict"])))
        status, body = call(srv.port, "POST", "/queries.json",
                            {"user": "u2", "num": 2})
        assert status == 200
        assert "prId" in body  # injected by feedback loop
        predicts = list(ctx.event_store.find("srvapp",
                                             event_names=["predict"]))
        assert len(predicts) == before + 1
        ev = predicts[-1]
        assert ev.entity_type == "pio_pr"
        assert ev.properties["query"] == {"user": "u2", "num": 2}
        assert ev.properties["prediction"]["itemScores"]

    def test_reload(self, served):
        ctx, srv = served
        status, body = call(srv.port, "POST", "/reload")
        assert status == 200
        assert body["engineInstanceId"]

    def test_stop(self, trained_ctx):
        ctx, engine, ep = trained_ctx
        srv = deploy(ctx, engine, ep, engine_id="srv", engine_version="1",
                     host="127.0.0.1", port=0)
        srv.start_background()
        status, body = call(srv.port, "POST", "/stop")
        assert status == 200
        import time
        stopped = False
        for _ in range(50):
            try:
                call(srv.port, "GET", "/status.json")
                time.sleep(0.05)
            except (ConnectionError, OSError):
                stopped = True
                break
        assert stopped, "server still answering after /stop"

    def test_accesskey_guard(self, trained_ctx):
        ctx, engine, ep = trained_ctx
        srv = deploy(ctx, engine, ep, engine_id="srv", engine_version="1",
                     config=ServerConfig(accesskey="SECRET"),
                     host="127.0.0.1", port=0)
        srv.start_background()
        try:
            assert call(srv.port, "POST", "/reload")[0] == 401
            assert call(srv.port, "POST",
                        "/reload?accessKey=SECRET")[0] == 200
            # queries are not key-guarded (parity with reference default)
            assert call(srv.port, "POST", "/queries.json",
                        {"user": "u1", "num": 1})[0] == 200
        finally:
            srv.shutdown()


class TestBatchPredict:
    def test_jsonl_roundtrip(self, trained_ctx, tmp_path):
        ctx, engine, ep = trained_ctx
        inp = tmp_path / "queries.jsonl"
        out = tmp_path / "predictions.jsonl"
        queries = [{"user": f"u{i}", "num": 3} for i in range(5)]
        inp.write_text("\n".join(json.dumps(q) for q in queries) + "\n\n")
        n = run_batch_predict(ctx, engine, ep, str(inp), str(out),
                              engine_id="srv", engine_version="1")
        assert n == 5
        lines = [json.loads(l) for l in out.read_text().splitlines()]
        assert len(lines) == 5
        for q, line in zip(queries, lines):
            assert line["query"] == q
            assert len(line["prediction"]["itemScores"]) == 3


class TestMicroBatching:
    def test_concurrent_queries_batched(self, trained_ctx):
        import threading

        ctx, engine, ep = trained_ctx
        srv = deploy(ctx, engine, ep, engine_id="srv", engine_version="1",
                     config=ServerConfig(batching=True, batch_window_ms=20,
                                         max_batch=16),
                     host="127.0.0.1", port=0)
        srv.start_background()
        try:
            # reference result without batching
            _, want = call(srv.port, "POST", "/queries.json",
                           {"user": "u1", "num": 3})

            results = [None] * 8
            def fire(i):
                _, results[i] = call(srv.port, "POST", "/queries.json",
                                     {"user": "u1", "num": 3})
            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            # same ranking; scores to float32 tolerance — the batched
            # dispatch compiles a different [B, n] shape whose reduction
            # order may differ from the B=1 kernel's by an ulp
            for r in results:
                assert [s["item"] for s in r["itemScores"]] == \
                    [s["item"] for s in want["itemScores"]]
                for got, exp in zip(r["itemScores"], want["itemScores"]):
                    assert got["score"] == pytest.approx(exp["score"],
                                                         rel=1e-5)
        finally:
            srv.shutdown()

    def test_bad_query_isolated_in_batch(self, trained_ctx):
        ctx, engine, ep = trained_ctx
        srv = deploy(ctx, engine, ep, engine_id="srv", engine_version="1",
                     config=ServerConfig(batching=True, batch_window_ms=5),
                     host="127.0.0.1", port=0)
        srv.start_background()
        try:
            status, body = call(srv.port, "POST", "/queries.json",
                                {"bogus": 1})
            assert status == 400
            status, body = call(srv.port, "POST", "/queries.json",
                                {"user": "u1", "num": 2})
            assert status == 200 and len(body["itemScores"]) == 2
        finally:
            srv.shutdown()


class TestShardingFindingsGauge:
    def test_census_recorded_and_on_status(self, trained_ctx):
        """The pio_sharding_findings info gauge (ISSUE 14): the server
        records the per-rule count of pragma-suppressed sharding
        findings baked into the deployed build, and /status.json
        carries the same census as `shardingFindings`."""
        from predictionio_tpu.analysis import count_sharding_pragmas
        from predictionio_tpu.server.engineserver import (
            QueryServer,
            build_app,
        )
        from predictionio_tpu.workflow import (
            get_latest_completed,
            load_models_for_deploy,
        )

        ctx, engine, ep = trained_ctx
        inst = get_latest_completed(ctx, engine_id="srv")
        models = load_models_for_deploy(ctx, engine, inst, ep)
        server = QueryServer(ctx, engine, ep, models, inst)
        expect = count_sharding_pragmas()
        sf = server.sharding_findings_status()
        assert sf["byRule"] == dict(sorted(expect.items()))
        assert sf["suppressed"] == sum(expect.values())
        rendered = server.metrics.render()
        for rule, n in expect.items():
            assert (f'pio_sharding_findings{{rule="{rule}"}} {n}'
                    in rendered)
        app = build_app(server)
        route = next(h for m, _, _, h in app._routes
                     if getattr(h, "__name__", "") == "status")
        doc = route(None).body
        assert doc["shardingFindings"] == sf


class TestServeErrorIsolation:
    def test_serve_error_isolated_in_mixed_batch(self, trained_ctx):
        """A serve-time exception for one query must not poison its
        batch-mates (one genuinely mixed batch through the staged
        stages)."""
        from conftest import serve_staged_batch

        from predictionio_tpu.server.engineserver import (
            HTTPError,
            QueryServer,
        )
        from predictionio_tpu.workflow import (
            get_latest_completed,
            load_models_for_deploy,
        )

        ctx, engine, ep = trained_ctx
        inst = get_latest_completed(ctx, engine_id="srv")
        models = load_models_for_deploy(ctx, engine, inst, ep)
        server = QueryServer(ctx, engine, ep, models, inst,
                             ServerConfig(batching=True, max_batch=8,
                                          warm_start=False))

        class PoisonServing:
            def __init__(self, inner):
                self.inner = inner

            def supplement(self, q):
                return self.inner.supplement(q)

            def serve(self, q, ps):
                if q.user == "u3":
                    raise RuntimeError("poison")
                return self.inner.serve(q, ps)

        server.serving = PoisonServing(server.serving)
        out, _ = serve_staged_batch(server, [
            {"user": "u1", "num": 2},
            {"user": "u3", "num": 2},   # serve raises
            {"bogus": 1},               # parse error
            {"user": "u5", "num": 2},
        ])
        assert len(out[0]["itemScores"]) == 2
        assert isinstance(out[1], HTTPError) and out[1].status == 500
        assert isinstance(out[2], HTTPError) and out[2].status == 400
        assert len(out[3]["itemScores"]) == 2


class TestRemoteLog:
    def test_remote_log_ships_and_swallows(self, trained_ctx):
        """remote_log POSTs {engineInstance, message} with the prefix
        (CreateServer.scala remoteLog :435-446) and swallows collector
        outages; 400s do not remote-log over HTTP."""
        import json as _json

        from predictionio_tpu.server.engineserver import QueryServer
        from predictionio_tpu.server.http import (
            AppServer,
            HTTPApp,
            Request,
            json_response,
        )
        from predictionio_tpu.workflow import (
            get_latest_completed,
            load_models_for_deploy,
        )

        received = []
        collector_app = HTTPApp("collector")

        @collector_app.route("POST", "/log")
        def log_sink(req: Request):
            received.append(req.body.decode())
            return json_response({"ok": True})

        collector = AppServer(collector_app, "127.0.0.1", 0)
        collector.start_background()
        try:
            ctx, engine, ep = trained_ctx
            cfg = ServerConfig(
                log_url=f"http://127.0.0.1:{collector.port}/log",
                log_prefix="PIO: ")

            # client errors do not remote-log
            srv = deploy(ctx, engine, ep, engine_id="srv",
                         engine_version="1", config=cfg,
                         host="127.0.0.1", port=0)
            srv.start_background()
            try:
                status, _ = call(srv.port, "POST", "/queries.json",
                                 {"bogus": 1})
                assert status == 400
                assert not received
            finally:
                srv.shutdown()

            inst = get_latest_completed(ctx, engine_id="srv")
            models = load_models_for_deploy(ctx, engine, inst, ep)
            qs = QueryServer(ctx, engine, ep, models, inst, cfg)
            qs.remote_log("boom", wait=True)
            assert received and received[-1].startswith("PIO: ")
            body = _json.loads(received[-1][len("PIO: "):])
            assert body["message"] == "boom"
            assert body["engineInstance"] == inst.id
        finally:
            collector.shutdown()
        qs.remote_log("after-shutdown", wait=True)  # down: must not raise


class TestPluginREST:
    def test_plugin_rest_route(self, trained_ctx):
        """/plugins/<type>/<name>/<args…> dispatches to handle_rest
        (CreateServer.scala:684-689)."""
        from predictionio_tpu.server.engineserver import QueryServer
        from predictionio_tpu.server.plugins import (
            EngineServerPlugin,
            EngineServerPlugins,
        )
        from predictionio_tpu.workflow import (
            get_latest_completed,
            load_models_for_deploy,
        )
        from predictionio_tpu.server.engineserver import (
            create_engine_server,
        )

        class EchoPlugin(EngineServerPlugin):
            plugin_name = "echo"
            plugin_description = "echoes its REST args"

            def process(self, query, prediction):
                return prediction

            def handle_rest(self, args):
                return {"args": args}

        ctx, engine, ep = trained_ctx
        inst = get_latest_completed(ctx, engine_id="srv")
        models = load_models_for_deploy(ctx, engine, inst, ep)
        plugins = EngineServerPlugins()
        plugins.register(EchoPlugin(), blocker=True)
        qs = QueryServer(ctx, engine, ep, models, inst, plugins=plugins)
        srv = create_engine_server(qs, "127.0.0.1", 0).start_background()
        try:
            status, body = call(srv.port, "GET",
                                "/plugins/outputblockers/echo/a/b")
            assert status == 200 and body == {"args": ["a", "b"]}
            status, body = call(srv.port, "GET", "/plugins.json")
            assert "echo" in body["plugins"]["outputblockers"]
            assert call(srv.port, "GET",
                        "/plugins/outputblockers/nope")[0] == 404
            assert call(srv.port, "GET",
                        "/plugins/badtype/echo")[0] == 404
        finally:
            srv.shutdown()


class TestServingWarmup:
    def test_warm_serving_flag_and_hook(self, trained_ctx):
        """ServerConfig.warm_start pre-compiles the serving shapes via
        the algorithm's warm_serving hook and flips /status.json's
        servingWarm (otherwise each cold batch shape costs an XLA
        compile DURING serving)."""
        from predictionio_tpu.server.engineserver import (
            QueryServer,
            ServerConfig,
        )
        from predictionio_tpu.workflow.core import (
            get_latest_completed,
            load_models_for_deploy,
        )

        ctx, engine, ep = trained_ctx
        inst = get_latest_completed(ctx, engine_id="srv")
        models = load_models_for_deploy(ctx, engine, inst, ep)

        # the hook exists on the shipped template and runs clean
        assert hasattr(engine.make_algorithms(ep)[0], "warm_serving")

        qs = QueryServer(ctx, engine, ep, models, inst,
                         ServerConfig(batching=True, max_batch=8))
        assert qs.warm_done.wait(timeout=60)

        # warm_start=False: no thread, immediately "warm"
        qs2 = QueryServer(ctx, engine, ep, models, inst,
                          ServerConfig(warm_start=False))
        assert qs2.warm_done.is_set()


def _make_server(models, cfg):
    """Minimal real QueryServer over a synthetic COMPLETED instance."""
    from predictionio_tpu.data.storage.base import (
        STATUS_COMPLETED,
        EngineInstance,
    )
    from predictionio_tpu.server.engineserver import QueryServer
    from predictionio_tpu.templates.recommendation import (
        default_engine_params,
        recommendation_engine,
    )

    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "resid"))
    ctx = Context(app_name="resid", _storage=storage)
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="r", status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="r", engine_version="1", engine_variant="e.json",
        engine_factory="f")
    return QueryServer(ctx, recommendation_engine(),
                       default_engine_params("resid", rank=8), models,
                       inst, cfg)


def test_bind_makes_large_model_device_resident(monkeypatch):
    """A re-materialized (numpy) model past HOST_SERVE_WORK must move
    to the device ONCE at bind — through the REAL QueryServer._bind ->
    prepare_serving_model wiring, not just the helper. Budget is
    monkeypatched tiny so the test model stays a few KB."""
    import numpy as np

    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models import als as als_mod
    from predictionio_tpu.models.als import ALSModel, ALSParams

    monkeypatch.setattr(als_mod, "HOST_SERVE_WORK", 1024)

    rank = 8
    def mk(n_items):
        return ALSModel(
            user_factors=np.zeros((4, rank), np.float32),
            item_factors=np.zeros((n_items, rank), np.float32),
            n_users=4, n_items=n_items,
            user_ids=BiMap({f"u{i}": i for i in range(4)}),
            item_ids=BiMap({f"i{i}": i for i in range(n_items)}),
            params=ALSParams(rank=rank))

    big = mk(1024 // rank + 8)     # past the (patched) batch-1 budget
    qs = _make_server([big], ServerConfig(warm_start=False))
    assert not isinstance(qs.models[0].item_factors, np.ndarray)

    small = mk(8)                  # host fast path stays host-resident
    qs2 = _make_server([small], ServerConfig(warm_start=False))
    assert isinstance(qs2.models[0].item_factors, np.ndarray)

    # batched binds use the BATCHED budget: the same small model past
    # max_batch * size must go to the device
    qs3 = _make_server([small], ServerConfig(warm_start=False,
                                             batching=True,
                                             max_batch=64))
    assert not isinstance(qs3.models[0].item_factors, np.ndarray)
