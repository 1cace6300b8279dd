"""CLI console, admin API, and dashboard tests
(SURVEY C23/C24/C25 parity)."""

import json
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from predictionio_tpu.cli import main
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import Storage

T0 = datetime(2026, 1, 1, tzinfo=timezone.utc)

MEM_ENV = {
    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
}


@pytest.fixture()
def storage():
    return Storage(env=MEM_ENV)


def run(storage, *argv) -> int:
    return main(list(argv), storage=storage)


class TestAppCommands:
    def test_app_lifecycle(self, storage, capsys):
        assert run(storage, "app", "new", "myapp",
                   "--description", "demo") == 0
        out = capsys.readouterr().out
        assert "Access Key:" in out
        # duplicate rejected
        assert run(storage, "app", "new", "myapp") == 1
        assert run(storage, "app", "list") == 0
        assert "myapp" in capsys.readouterr().out
        assert run(storage, "app", "show", "myapp") == 0
        assert run(storage, "app", "delete", "myapp", "-f") == 0
        assert storage.apps().get_by_name("myapp") is None

    def test_channels(self, storage):
        run(storage, "app", "new", "chapp")
        assert run(storage, "app", "channel-new", "chapp", "mobile") == 0
        assert any(c.name == "mobile" for c in storage.channels()
                   .get_by_app_id(storage.apps().get_by_name("chapp").id))
        # invalid channel name
        assert run(storage, "app", "channel-new", "chapp",
                   "bad name!") == 1
        assert run(storage, "app", "channel-delete", "chapp", "mobile",
                   "-f") == 0

    def test_accesskey_commands(self, storage, capsys):
        run(storage, "app", "new", "akapp")
        assert run(storage, "accesskey", "new", "akapp", "view", "buy",
                   "--key", "SECRET") == 0
        assert run(storage, "accesskey", "list", "--app", "akapp") == 0
        out = capsys.readouterr().out
        assert "SECRET" in out and "view,buy" in out
        assert run(storage, "accesskey", "delete", "SECRET") == 0

    def test_data_delete(self, storage):
        run(storage, "app", "new", "dapp")
        app_id = storage.apps().get_by_name("dapp").id
        storage.events().insert(Event(
            event="view", entity_type="user", entity_id="u1",
            target_entity_type="item", target_entity_id="i1",
            event_time=T0), app_id)
        assert run(storage, "app", "data-delete", "dapp", "-f") == 0
        from predictionio_tpu.data.storage.base import EventFilter
        assert list(storage.events().find(app_id, None, EventFilter())) == []


class TestStatusVersionTemplate:
    def test_status(self, storage, capsys):
        assert run(storage, "status") == 0
        assert "ready to go" in capsys.readouterr().out

    def test_version(self, storage, capsys):
        assert run(storage, "version") == 0

    def test_template_list(self, storage, capsys):
        assert run(storage, "template") == 0
        assert "recommendation" in capsys.readouterr().out


def seed_ratings(storage, app_name="cliapp"):
    run(storage, "app", "new", app_name)
    app_id = storage.apps().get_by_name(app_name).id
    rng = np.random.default_rng(2)
    events = []
    t = T0
    for u in range(20):
        pool = range(0, 8) if u % 2 == 0 else range(8, 16)
        for i in rng.choice(list(pool), size=5, replace=False):
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": 5.0}), event_time=t))
            t += timedelta(minutes=1)
    storage.events().insert_batch(events, app_id)
    return app_id


def write_variant(tmp_path, app_name="cliapp"):
    variant = {
        "id": "cli-engine",
        "version": "1",
        "engineFactory":
            "predictionio_tpu.templates.recommendation:"
            "recommendation_engine",
        "datasource": {"params": {"app_name": app_name}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 8, "num_iterations": 5,
                                   "seed": 4}}],
    }
    path = tmp_path / "engine.json"
    path.write_text(json.dumps(variant))
    return str(path)


class TestTrainBatchPredict:
    def test_build_train_batchpredict(self, storage, tmp_path, capsys):
        seed_ratings(storage)
        ej = write_variant(tmp_path)
        assert run(storage, "build", "--engine-json", ej) == 0
        assert run(storage, "train", "--engine-json", ej) == 0
        out = capsys.readouterr().out
        assert "Training completed" in out
        qfile = tmp_path / "queries.jsonl"
        qfile.write_text('{"user": "u0", "num": 3}\n'
                         '{"user": "u1", "num": 2}\n')
        ofile = tmp_path / "out.jsonl"
        assert run(storage, "batchpredict", "--engine-json", ej,
                   "--input", str(qfile), "--output", str(ofile)) == 0
        lines = [json.loads(l) for l in
                 ofile.read_text().strip().splitlines()]
        assert len(lines) == 2
        assert len(lines[0]["prediction"]["itemScores"]) == 3

    def test_export_import_roundtrip(self, storage, tmp_path):
        app_id = seed_ratings(storage, "exapp")
        out = tmp_path / "events.jsonl"
        assert run(storage, "export", "--app", "exapp",
                   "--output", str(out)) == 0
        n_lines = len(out.read_text().strip().splitlines())
        assert n_lines == 100  # 20 users × 5 ratings
        run(storage, "app", "new", "imapp")
        assert run(storage, "import", "--app", "imapp",
                   "--input", str(out)) == 0
        from predictionio_tpu.data.storage.base import EventFilter
        im_id = storage.apps().get_by_name("imapp").id
        got = list(storage.events().find(im_id, None, EventFilter()))
        assert len(got) == n_lines


class TestAdminServer:
    def test_admin_routes(self, storage):
        from predictionio_tpu.server.adminserver import build_app
        from predictionio_tpu.server.http import Request

        app = build_app(storage)

        def call(method, path, body=None):
            req = Request(method=method, path=path, query={}, headers={},
                          body=json.dumps(body).encode() if body else b"")
            resp = app.handle(req)
            return resp.status, (json.loads(resp.encoded())
                                 if resp.encoded() else None)

        status, body = call("GET", "/")
        assert status == 200 and body["status"] == "alive"
        status, body = call("POST", "/cmd/app", {"name": "adminapp"})
        assert body["status"] == 1 and body["key"]
        status, body = call("POST", "/cmd/app", {"name": "adminapp"})
        assert body["status"] == 0  # duplicate
        status, body = call("GET", "/cmd/app")
        assert any(a["name"] == "adminapp" for a in body["apps"])
        status, body = call("DELETE", "/cmd/app/adminapp/data")
        assert body["status"] == 1
        status, body = call("DELETE", "/cmd/app/adminapp")
        assert body["status"] == 1
        assert storage.apps().get_by_name("adminapp") is None
        status, body = call("DELETE", "/cmd/app/ghost")
        assert status == 404


class TestDashboard:
    def test_dashboard_routes(self, storage):
        from predictionio_tpu.data.storage.base import (
            STATUS_EVALCOMPLETED, EvaluationInstance)
        from predictionio_tpu.server.dashboard import build_app
        from predictionio_tpu.server.http import Request

        iid = storage.evaluation_instances().insert(EvaluationInstance(
            id="", status=STATUS_EVALCOMPLETED, start_time=T0, end_time=T0,
            evaluation_class="my.Eval",
            evaluator_results="Precision@10: 0.5",
            evaluator_results_html="<html>ok</html>",
            evaluator_results_json='{"metric": 0.5}'))
        app = build_app(storage)

        def call(path):
            return app.handle(Request(method="GET", path=path, query={},
                                      headers={}, body=b""))

        index = call("/")
        assert index.status == 200
        assert "my.Eval" in index.encoded().decode()
        txt = call(f"/engine_instances/{iid}/evaluator_results.txt")
        assert txt.encoded().decode() == "Precision@10: 0.5"
        html = call(f"/engine_instances/{iid}/evaluator_results.html")
        assert "ok" in html.encoded().decode()
        js = call(f"/engine_instances/{iid}/evaluator_results.json")
        assert json.loads(js.encoded())["metric"] == 0.5
        cors = call(f"/engine_instances/{iid}/local_evaluator_results.json")
        assert cors.headers.get("Access-Control-Allow-Origin") == "*"
        assert call("/engine_instances/nope/evaluator_results.txt")\
            .status == 404


class TestEvalCommand:
    def test_eval(self, storage, tmp_path, capsys, monkeypatch):
        seed_ratings(storage, "evapp")
        mod = tmp_path / "cli_eval_mod.py"
        mod.write_text('''
from predictionio_tpu.controller import Evaluation
from predictionio_tpu.controller.params import EngineParams
from predictionio_tpu.models.als import ALSParams
from predictionio_tpu.templates.recommendation import (
    DataSourceParams, PrecisionAtK, recommendation_engine)

evaluation = Evaluation(engine=recommendation_engine(),
                        metric=PrecisionAtK(k=3, rating_threshold=2.0))
engine_params_list = [
    EngineParams(
        datasource=("", DataSourceParams(app_name="evapp", eval_k=2)),
        algorithms=[("als", ALSParams(rank=r, num_iterations=4, seed=1))])
    for r in (4, 8)]


class Gen:
    engine_params_list = engine_params_list


gen = Gen()
''')
        monkeypatch.syspath_prepend(str(tmp_path))
        assert run(storage, "eval", "cli_eval_mod:evaluation",
                   "cli_eval_mod:gen") == 0
        out = capsys.readouterr().out
        assert "Precision@3" in out or "0." in out


class TestTrainWorkflowFlags:
    def test_stop_after_read(self, storage, tmp_path, capsys):
        """--stop-after-read leaves the instance in INIT (reference
        WorkflowParams semantics)."""
        seed_ratings(storage, "flagapp")
        ej = write_variant(tmp_path, "flagapp")
        assert run(storage, "train", "--engine-json", ej,
                   "--stop-after-read") == 0
        from predictionio_tpu.data.storage.base import STATUS_INIT
        instances = storage.engine_instances().get_all()
        assert instances
        assert all(i.status == STATUS_INIT for i in instances)

    def test_stop_after_prepare(self, storage, tmp_path):
        seed_ratings(storage, "flagapp2")
        ej = write_variant(tmp_path, "flagapp2")
        assert run(storage, "train", "--engine-json", ej,
                   "--stop-after-prepare") == 0
        from predictionio_tpu.data.storage.base import STATUS_INIT
        assert all(i.status == STATUS_INIT
                   for i in storage.engine_instances().get_all())

    def test_skip_sanity_check_trains(self, storage, tmp_path, capsys):
        """An app with no events fails the sanity check — unless the
        flag actually reaches the workflow."""
        run(storage, "app", "new", "emptyapp")
        ej = write_variant(tmp_path, "emptyapp")
        with pytest.raises(ValueError, match="no ratings"):
            run(storage, "train", "--engine-json", ej)
        # with the flag the sanity check is SKIPPED: the failure moves
        # past it into the algorithm (a different, later error)
        with pytest.raises(ValueError, match="non-empty ratings matrix"):
            run(storage, "train", "--engine-json", ej,
                "--skip-sanity-check")
        # success path: flag on a HEALTHY app still trains to COMPLETED
        seed_ratings(storage, "flagok")
        ej2 = write_variant(tmp_path, "flagok")
        assert run(storage, "train", "--engine-json", ej2,
                   "--skip-sanity-check") == 0
        assert "Training completed" in capsys.readouterr().out


class TestAdminDashboardAuth:
    def test_admin_accesskey_guard(self, storage):
        from predictionio_tpu.server.adminserver import build_app
        from predictionio_tpu.server.http import Request

        app = build_app(storage, accesskey="SECRET")

        def call(path, query=None):
            return app.handle(Request(method="GET", path=path,
                                      query=query or {}, headers={},
                                      body=b"")).status

        assert call("/") == 200               # liveness stays open
        assert call("/cmd/app") == 401
        assert call("/cmd/app", {"accessKey": "SECRET"}) == 200

    def test_dashboard_accesskey_guard(self, storage):
        from predictionio_tpu.server.dashboard import build_app
        from predictionio_tpu.server.http import Request

        app = build_app(storage, accesskey="SECRET")

        def call(path, query=None):
            return app.handle(Request(method="GET", path=path,
                                      query=query or {}, headers={},
                                      body=b"")).status

        assert call("/") == 401
        assert call("/", {"accessKey": "SECRET"}) == 200

    def test_dashboard_session_cookie_keeps_links_clean(self, storage):
        """First authenticated request mints an HttpOnly session cookie;
        generated links never embed the accessKey (browser history /
        proxy logs / Referer leakage — ADVICE r1)."""
        from datetime import datetime, timezone

        from predictionio_tpu.data.storage.base import (
            STATUS_EVALCOMPLETED,
            EvaluationInstance,
        )
        from predictionio_tpu.server.dashboard import build_app
        from predictionio_tpu.server.http import Request

        t = datetime(2026, 1, 1, tzinfo=timezone.utc)
        storage.evaluation_instances().insert(EvaluationInstance(
            id="", status=STATUS_EVALCOMPLETED, start_time=t, end_time=t,
            evaluator_results="r"))
        app = build_app(storage, accesskey="SECRET")
        resp = app.handle(Request(method="GET", path="/",
                                  query={"accessKey": "SECRET"},
                                  headers={}, body=b""))
        html = resp.encoded().decode()
        assert "accessKey" not in html        # links carry no secret
        cookie = resp.headers.get("Set-Cookie", "")
        assert "HttpOnly" in cookie
        # the minted cookie authenticates follow-up requests on its own
        token = cookie.split(";")[0]
        resp2 = app.handle(Request(method="GET", path="/", query={},
                                   headers={"Cookie": token}, body=b""))
        assert resp2.status == 200
        # and a bogus cookie does not
        resp3 = app.handle(Request(
            method="GET", path="/", query={},
            headers={"Cookie": "pio_dashboard_session=forged"}, body=b""))
        assert resp3.status == 401


class TestStartStopAll:
    """`ptpu start-all` / `stop-all` (VERDICT r3 missing #3): the
    bin/pio-start-all role — daemons with pidfiles, ports answering,
    double-start refused, stop-all reaps everything."""

    def test_round_trip(self, storage, tmp_path, capsys):
        import os
        import socket

        def free_port():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        ev_p, ad_p, db_p = free_port(), free_port(), free_port()
        pid_dir = str(tmp_path / "pids")
        env_before = dict(os.environ)
        os.environ.update(MEM_ENV)
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            rc = run(storage, "start-all", "--ip", "127.0.0.1",
                     "--pid-dir", pid_dir,
                     "--eventserver-port", str(ev_p),
                     "--adminserver-port", str(ad_p),
                     "--dashboard-port", str(db_p),
                     "--start-timeout", "60")
            assert rc == 0, capsys.readouterr()
            for name, port in (("eventserver", ev_p),
                               ("adminserver", ad_p),
                               ("dashboard", db_p)):
                assert os.path.exists(
                    os.path.join(pid_dir, f"{name}.pid"))
                with socket.create_connection(("127.0.0.1", port),
                                              timeout=5):
                    pass
            pids = {n: int(open(os.path.join(pid_dir, f"{n}.pid"))
                           .read())
                    for n in ("eventserver", "adminserver",
                              "dashboard")}
            # double start must refuse, not spawn twins
            rc2 = run(storage, "start-all", "--ip", "127.0.0.1",
                      "--pid-dir", pid_dir,
                      "--eventserver-port", str(ev_p),
                      "--adminserver-port", str(ad_p),
                      "--dashboard-port", str(db_p))
            assert rc2 == 1
            for n, pid in pids.items():
                assert int(open(os.path.join(pid_dir, f"{n}.pid"))
                           .read()) == pid
        finally:
            rc3 = run(storage, "stop-all", "--pid-dir", pid_dir)
            os.environ.clear()
            os.environ.update(env_before)
        assert rc3 == 0
        import errno
        for n, pid in pids.items():
            assert not os.path.exists(
                os.path.join(pid_dir, f"{n}.pid"))
            try:
                os.kill(pid, 0)
                alive = True
            except ProcessLookupError:
                alive = False
            assert not alive, f"{n} pid {pid} survived stop-all"


def test_deploy_batching_defaults_match_config():
    """`ptpu deploy`'s batching flag defaults must equal ServerConfig's
    field defaults (the CLI uses literals so storage-only commands
    never import the server stack / jax — this test is the sync)."""
    from predictionio_tpu.cli import build_parser
    from predictionio_tpu.server.engineserver import (
        ServerConfig,
        StagedPipeline,
    )

    args = build_parser().parse_args(
        ["deploy", "--engine-json", "engine.json"])
    cfg = ServerConfig()
    assert args.max_batch == cfg.max_batch
    assert args.batch_window_ms == cfg.batch_window_ms
    assert args.batch_pipeline == cfg.batch_pipeline
    assert args.serving_mode == cfg.serving_mode
    # staged-pipeline knobs (ISSUE 9) stay in sync the same way
    assert args.queue_deadline_ms == cfg.queue_deadline_ms
    assert args.assemble_workers == cfg.assemble_workers
    assert args.readback_workers == cfg.readback_workers
    assert args.pipeline_depth == cfg.pipeline_depth
    # serving fast-path knob (ISSUE 13) stays in sync the same way
    assert args.serving_quant == cfg.serving_quant
    # tracing knobs (ISSUE 12) stay in sync the same way
    assert (not args.no_trace) == cfg.tracing
    assert args.trace_ring == cfg.trace_ring
    assert args.trace_slow_ms == cfg.trace_slow_ms
    assert args.access_log_sample == cfg.access_log_sample
    # hot-key telemetry (ISSUE 17) stays in sync the same way
    assert args.hot_keys_k == cfg.hot_keys_k
    import inspect

    sig = inspect.signature(StagedPipeline.__init__)
    assert sig.parameters["max_batch"].default == cfg.max_batch
