"""``ptpu`` console — the framework's CLI.

Capability parity with the reference ``pio`` console
(``tools/src/main/scala/org/apache/predictionio/tools/console/
Console.scala:80-650`` subcommands; command objects under
``tools/.../commands/``): app/accesskey/channel management, build (a
no-op venv check here — no sbt), train, eval, deploy, undeploy,
batchpredict, eventserver, adminserver, dashboard, status, export,
import, version, template stubs.

Where the reference shells out to ``spark-submit`` (``Runner.scala:185``),
this console runs the workflow in-process against the JAX mesh — there is
no separate driver JVM to launch.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from typing import Any, List, Optional

from .. import __version__
from ..data.storage.base import AccessKey, App, Channel
from ..data.storage.registry import Storage, get_storage


def _out(msg: str) -> None:
    print(msg)


def _err(msg: str) -> None:
    print(msg, file=sys.stderr)


# ---------------------------------------------------------------------------
# engine.json loading (the reference's engine variant,
# WorkflowUtils.getEngine + jValueToEngineParams)
# ---------------------------------------------------------------------------

def load_variant(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def load_engine_factory(spec: str):
    """Resolve ``module.path:callable`` (the reflective ``EngineFactory``
    lookup, ``WorkflowUtils.scala:53-88``)."""
    if ":" not in spec:
        raise SystemExit(f"engineFactory must look like "
                         f"'package.module:factory', got {spec!r}")
    mod_name, attr = spec.split(":", 1)
    try:
        mod = importlib.import_module(mod_name)
    except ImportError as e:
        raise SystemExit(f"Cannot import engine factory module "
                         f"{mod_name!r}: {e}")
    try:
        factory = getattr(mod, attr)
    except AttributeError:
        raise SystemExit(f"Module {mod_name!r} has no attribute {attr!r}")
    return factory


def engine_from_variant(variant: dict):
    factory = load_engine_factory(variant.get("engineFactory", ""))
    engine = factory() if callable(factory) else factory
    engine_params = engine.params_from_variant(variant)
    return engine, engine_params


# ---------------------------------------------------------------------------
# subcommand implementations (tools/.../commands/*.scala)
# ---------------------------------------------------------------------------

def cmd_app(args, storage: Storage) -> int:
    apps = storage.apps()
    keys = storage.access_keys()
    chans = storage.channels()
    sub = args.app_command
    if sub == "new":
        if apps.get_by_name(args.name) is not None:
            _err(f"App {args.name} already exists. Aborting.")
            return 1
        app_id = apps.insert(App(id=args.id or 0, name=args.name,
                                 description=args.description))
        if app_id is None:
            _err(f"Unable to create app {args.name} (ID conflict?). "
                 f"Aborting.")
            return 1
        storage.events().init(app_id)
        key = keys.insert(AccessKey(key=args.access_key or "",
                                    app_id=app_id, events=()))
        if key is None:
            _err(f"Unable to create access key (duplicate?). Aborting.")
            return 1
        _out(f"Initialized Event Store for this app ID: {app_id}.")
        _out(f"Created new app:")
        _out(f"      Name: {args.name}")
        _out(f"        ID: {app_id}")
        _out(f"Access Key: {key}")
        return 0
    if sub == "list":
        _out(f"{'Name':20} |   ID | Access Key")
        for a in sorted(apps.get_all(), key=lambda a: a.name):
            for k in keys.get_by_app_id(a.id) or [None]:
                key = k.key if k else ""
                allowed = (",".join(k.events) if k and k.events
                           else "(all)")
                _out(f"{a.name:20} | {a.id:4} | {key} | {allowed}")
        _out(f"Finished listing {len(apps.get_all())} app(s).")
        return 0
    if sub == "show":
        a = apps.get_by_name(args.name)
        if a is None:
            _err(f"App {args.name} does not exist. Aborting.")
            return 1
        _out(f"    App Name: {a.name}")
        _out(f"      App ID: {a.id}")
        _out(f" Description: {a.description or ''}")
        for k in keys.get_by_app_id(a.id):
            allowed = ",".join(k.events) if k.events else "(all)"
            _out(f"  Access Key: {k.key} | {allowed}")
        for c in chans.get_by_app_id(a.id):
            _out(f"     Channel: {c.name} (ID {c.id})")
        return 0
    if sub == "delete":
        a = apps.get_by_name(args.name)
        if a is None:
            _err(f"App {args.name} does not exist. Aborting.")
            return 1
        if not args.force and not _confirm(
                f"Delete app {args.name} and ALL its data?"):
            return 1
        for c in chans.get_by_app_id(a.id):
            storage.events().remove(a.id, c.id)
            chans.delete(c.id)
        storage.events().remove(a.id)
        for k in keys.get_by_app_id(a.id):
            keys.delete(k.key)
        apps.delete(a.id)
        _out(f"Deleted app {args.name}.")
        return 0
    if sub == "data-delete":
        a = apps.get_by_name(args.name)
        if a is None:
            _err(f"App {args.name} does not exist. Aborting.")
            return 1
        if not args.force and not _confirm(
                f"Delete ALL data of app {args.name}?"):
            return 1
        channel_id = None
        if args.channel:
            ch = _find_channel(storage, a, args.channel)
            if ch is None:
                _err(f"Channel {args.channel} does not exist. Aborting.")
                return 1
            channel_id = ch.id
        storage.events().remove(a.id, channel_id)
        storage.events().init(a.id, channel_id)
        _out(f"Removed Event Store for the app ID: {a.id}")
        return 0
    if sub == "channel-new":
        a = apps.get_by_name(args.name)
        if a is None:
            _err(f"App {args.name} does not exist. Aborting.")
            return 1
        if not Channel.is_valid_name(args.channel):
            _err(f"Channel name {args.channel} is invalid (1-16 "
                 f"alphanumeric/dash characters). Aborting.")
            return 1
        if any(c.name == args.channel for c in chans.get_by_app_id(a.id)):
            _err(f"Channel {args.channel} already exists. Aborting.")
            return 1
        cid = chans.insert(Channel(id=0, name=args.channel, app_id=a.id))
        storage.events().init(a.id, cid)
        _out(f"Created channel {args.channel} (ID {cid}) for app "
             f"{args.name}.")
        return 0
    if sub == "channel-delete":
        a = apps.get_by_name(args.name)
        if a is None:
            _err(f"App {args.name} does not exist. Aborting.")
            return 1
        ch = _find_channel(storage, a, args.channel)
        if ch is None:
            _err(f"Channel {args.channel} does not exist. Aborting.")
            return 1
        if not args.force and not _confirm(
                f"Delete channel {args.channel} and its data?"):
            return 1
        storage.events().remove(a.id, ch.id)
        chans.delete(ch.id)
        _out(f"Deleted channel {args.channel}.")
        return 0
    _err(f"Unknown app subcommand {sub!r}")
    return 1


def cmd_accesskey(args, storage: Storage) -> int:
    keys = storage.access_keys()
    apps = storage.apps()
    sub = args.ak_command
    if sub == "new":
        a = apps.get_by_name(args.app)
        if a is None:
            _err(f"App {args.app} does not exist. Aborting.")
            return 1
        key = keys.insert(AccessKey(key=args.key or "", app_id=a.id,
                                    events=tuple(args.events or ())))
        if key is None:
            _err("Unable to create access key (duplicate?). Aborting.")
            return 1
        _out(f"Created new access key: {key}")
        return 0
    if sub == "list":
        rows = keys.get_all()
        if args.app:
            a = apps.get_by_name(args.app)
            if a is None:
                _err(f"App {args.app} does not exist. Aborting.")
                return 1
            rows = keys.get_by_app_id(a.id)
        for k in rows:
            allowed = ",".join(k.events) if k.events else "(all)"
            _out(f"{k.key} | app {k.app_id} | {allowed}")
        _out(f"Finished listing {len(rows)} access key(s).")
        return 0
    if sub == "delete":
        keys.delete(args.key)
        _out(f"Deleted access key {args.key}.")
        return 0
    _err(f"Unknown accesskey subcommand {sub!r}")
    return 1


def _make_ctx(storage: Storage, app_name: str = ""):
    from ..controller.context import Context
    return Context(app_name=app_name, _storage=storage)


def cmd_train(args, storage: Storage) -> int:
    from ..workflow import run_train

    variant = load_variant(args.engine_json)
    engine, engine_params = engine_from_variant(variant)
    ctx = _make_ctx(storage)
    ctx = ctx.copy(skip_sanity_check=args.skip_sanity_check,
                   stop_after_read=args.stop_after_read,
                   stop_after_prepare=args.stop_after_prepare)
    instance_id = run_train(
        ctx, engine, engine_params,
        engine_id=args.engine_id or variant.get("id", "default"),
        engine_version=args.engine_version or variant.get("version", "1"),
        engine_variant=args.engine_json,
        engine_factory=variant.get("engineFactory", ""))
    if args.stop_after_read or args.stop_after_prepare:
        stage = "read" if args.stop_after_read else "prepare"
        _out(f"Workflow stopped after {stage} (instance {instance_id} "
             f"left in INIT).")
    else:
        if ctx.stage_timings:
            _out(f"Train stages: {json.dumps(ctx.stage_timings)}")
        for label, key in (("Train build info", "train_build_info"),
                           ("Train kernels", "train_kernels")):
            if ctx.extra.get(key):
                _out(f"{label}: {json.dumps(ctx.extra[key])}")
        _out(f"Training completed. Engine instance ID: {instance_id}")
    return 0


def cmd_eval(args, storage: Storage) -> int:
    from ..workflow import run_evaluation

    evaluation = load_engine_factory(args.evaluation)
    if callable(evaluation) and not hasattr(evaluation, "engine"):
        evaluation = evaluation()
    params_list = None
    if args.engine_params_generator:
        gen = load_engine_factory(args.engine_params_generator)
        if callable(gen) and not hasattr(gen, "engine_params_list"):
            gen = gen()
        params_list = list(gen.engine_params_list)
    elif getattr(evaluation, "engine_params_list", None):
        params_list = list(evaluation.engine_params_list)
    if not params_list:
        _err("No engine params to evaluate; provide an engine params "
             "generator.")
        return 1
    ctx = _make_ctx(storage)
    result = run_evaluation(
        ctx, evaluation, params_list,
        evaluation_class=args.evaluation,
        params_generator_class=args.engine_params_generator or "",
        parallelism=max(1, args.parallelism))
    _out(result.to_one_liner())
    return 0


def cmd_deploy(args, storage: Storage) -> int:
    from ..server.engineserver import ServerConfig, deploy

    variant = load_variant(args.engine_json)
    engine, engine_params = engine_from_variant(variant)
    ctx = _make_ctx(storage)
    from ..server.http import ssl_context_from

    config = ServerConfig(
        feedback=args.feedback,
        feedback_app_name=args.feedback_app_name or None,
        accesskey=args.accesskey or None,
        batching=args.batching,
        max_batch=args.max_batch,
        batch_window_ms=args.batch_window_ms,
        batch_pipeline=args.batch_pipeline,
        queue_deadline_ms=args.queue_deadline_ms,
        assemble_workers=args.assemble_workers,
        readback_workers=args.readback_workers,
        pipeline_depth=args.pipeline_depth,
        serving_cache=args.cache,
        cache_entries=args.cache_entries,
        cache_ttl_sec=args.cache_ttl,
        feature_ttl_sec=args.feature_ttl,
        hot_entities=args.hot_entities,
        debug_locks=args.debug_locks,
        serving_mode=args.serving_mode,
        serving_quant=args.serving_quant,
        streaming=args.stream,
        stream_app_name=args.stream_app or None,
        stream_interval_ms=args.stream_interval_ms,
        stream_max_events=args.stream_max_events,
        stream_consumer=args.stream_consumer,
        stream_drift_threshold=args.stream_drift_threshold,
        stream_canary_probes=args.stream_canary_probes,
        faults=args.faults or None,
        tracing=not args.no_trace,
        trace_ring=args.trace_ring,
        trace_slow_ms=args.trace_slow_ms,
        access_log_sample=args.access_log_sample,
        profile_dir=args.profile_dir or None,
        slo_specs=args.slo_specs or None,
        slo_interval_ms=args.slo_interval_ms,
        hot_keys_k=args.hot_keys_k,
        artifact_dir=args.artifact_dir or None)
    ssl_ctx = ssl_context_from(args.cert or None, args.key or None)
    scheme = "https" if ssl_ctx else "http"
    if args.fleet_of > 1:
        # fleet deploy (ISSUE 17 + 18, docs/fleet.md,
        # docs/autoscaling.md): N replicas on consecutive ports, each
        # a full engine server, fronted by the entity-affinity query
        # router AND the fleet aggregator (merged metrics, fleet SLO,
        # cross-replica traces). With --autoscale, the replica
        # lifecycle manager + control loop grow/shrink the fleet
        # between --min-replicas and --max-replicas. The aggregator
        # holds the foreground; everything else runs in background
        # threads of this process.
        from ..fleet import FleetConfig, create_fleet_server
        from ..router import (
            Autoscaler,
            AutoscalePolicy,
            QueryRouter,
            ReplicaLifecycle,
            RouterConfig,
            create_router_server,
        )

        def _boot_replica(port: int):
            srv = deploy(
                ctx, engine, engine_params,
                engine_id=args.engine_id or variant.get("id", "default"),
                engine_version=(args.engine_version
                                or variant.get("version", "1")),
                engine_variant=args.engine_json,
                config=config, host=args.ip, port=port,
                ssl_context=ssl_ctx)
            srv.start_background()
            return srv

        servers = [_boot_replica(args.port + i)
                   for i in range(args.fleet_of)]
        for srv in servers:
            _out(f"Replica live at {scheme}://{args.ip}:{srv.port}.")
        fleet_cfg = FleetConfig(
            replicas=[f"{scheme}://127.0.0.1:{srv.port}"
                      for srv in servers],
            scrape_interval_sec=args.fleet_scrape_interval_ms / 1000.0,
            slo_specs=args.slo_specs or None,
            slo_interval_sec=args.slo_interval_ms / 1000.0,
            capacity_path=args.capacity or None,
            accesskey=args.accesskey or None)
        agg, fleet_srv = create_fleet_server(
            fleet_cfg, host=args.ip, port=args.fleet_port,
            ssl_context=ssl_ctx)
        # the router registers its pio_router_* families on the
        # aggregator's registry so they ride the fleet /metrics
        # alongside the merged replica series and pio_autoscale_*
        router = QueryRouter(
            RouterConfig(accesskey=args.accesskey or None),
            registry=agg.registry)
        router_srv = create_router_server(router, host=args.ip,
                                          port=args.router_port,
                                          ssl_context=ssl_ctx)
        router_srv.start_background()
        agg.attach_router(router)
        # the aggregator's liveness view vetoes routing candidates;
        # "unknown"/"absent" (not yet scraped) is no opinion, so a
        # fresh replica isn't vetoed during its first scrape window
        router.set_health(
            lambda name: {"up": True, "down": False}.get(
                agg.replica_health(name)))
        lifecycle = ReplicaLifecycle(
            spawn=lambda: ((lambda srv:
                            (f"{scheme}://127.0.0.1:{srv.port}",
                             srv.shutdown))(_boot_replica(0))),
            router=router, aggregator=agg,
            registry=agg.registry,
            accesskey=args.accesskey or None)
        for srv in servers:
            lifecycle.adopt(f"{scheme}://127.0.0.1:{srv.port}",
                            stop_fn=srv.shutdown)
        autoscaler = None
        if args.autoscale:
            autoscaler = Autoscaler(
                agg, lifecycle,
                AutoscalePolicy(min_replicas=args.min_replicas,
                                max_replicas=args.max_replicas),
                registry=agg.registry).start()
            agg.attach_autoscaler(autoscaler)
            _out(f"Autoscaler running: {args.min_replicas}-"
                 f"{args.max_replicas} replicas, knee model "
                 f"{'loaded' if agg.capacity_signals()['kneeQps'] else 'ABSENT'}.")
        _out(f"Query router live at "
             f"{scheme}://{args.ip}:{router_srv.port} — send "
             f"/queries.json here (entity-affinity + retry + spill).")
        _out(f"Fleet aggregator live at "
             f"{scheme}://{args.ip}:{fleet_srv.port} — merged "
             f"/metrics, /fleet.json, /route.json, /trace.json, "
             f"/hotkeys.json.")
        try:
            fleet_srv.serve_forever()
        except KeyboardInterrupt:
            _out("Shutting down.")
            if autoscaler is not None:
                autoscaler.stop()
            lifecycle.close(stop_replicas=True)
            router_srv.shutdown()
            agg.stop()
        return 0
    server = deploy(
        ctx, engine, engine_params,
        engine_id=args.engine_id or variant.get("id", "default"),
        engine_version=args.engine_version or variant.get("version", "1"),
        engine_variant=args.engine_json,
        config=config, host=args.ip, port=args.port, ssl_context=ssl_ctx)
    _out(f"Engine is deployed and running. Engine API is live at "
         f"{scheme}://{args.ip}:{server.port}.")
    _out(f"Telemetry: {scheme}://{args.ip}:{server.port}/metrics "
         f"(Prometheus) and /status.json.")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        _out("Shutting down.")
    warm_error = server.query_server.warm_error
    if warm_error is not None:
        _err(f"Deploy failed: serving warm-up failed: {warm_error}")
        return 1
    return 0


def _server_ssl_kwargs(args) -> dict:
    import ssl as _ssl

    kw = {}
    if getattr(args, "https", False):
        ctx = _ssl.create_default_context()
        if getattr(args, "insecure", False):
            # opt-in for self-signed local certs; the accessKey rides
            # this URL, so verification stays on by default
            ctx.check_hostname = False
            ctx.verify_mode = _ssl.CERT_NONE
        kw["context"] = ctx
    return kw


def _server_call(args, path: str, method: str = "GET",
                 body: Optional[dict] = None, timeout: float = 30.0):
    """One control-plane round trip to the deployed engine server;
    returns the parsed JSON body (raises on transport errors; HTTP
    error responses raise urllib's HTTPError with the JSON body)."""
    import urllib.request

    scheme = "https" if getattr(args, "https", False) else "http"
    url = f"{scheme}://{args.ip}:{args.port}{path}"
    if getattr(args, "accesskey", ""):
        sep = "&" if "?" in url else "?"
        url += f"{sep}accessKey={args.accesskey}"
    data = json.dumps(body).encode("utf-8") if body is not None else \
        (b"" if method == "POST" else None)
    req = urllib.request.Request(url, method=method, data=data)
    with urllib.request.urlopen(req, timeout=timeout,
                                **_server_ssl_kwargs(args)) as resp:
        raw = resp.read()
    return json.loads(raw) if raw else None


def cmd_undeploy(args, storage: Storage) -> int:
    # learn which release is being taken off traffic BEFORE stopping it
    # (the undeploy must land in the release history — ISSUE 3)
    info = None
    try:
        info = _server_call(args, "/status.json")
    except Exception:  # noqa: BLE001 — liveness is checked by /stop below
        pass
    try:
        _server_call(args, "/stop", method="POST", timeout=10)
    except Exception as e:  # noqa: BLE001 — report, don't traceback
        _err(f"Cannot undeploy {args.ip}:{args.port}: {e}")
        return 1
    if info and info.get("engineId"):
        _out(f"Undeployed engine server at {args.ip}:{args.port} "
             f"(engine {info['engineId']}, release instance "
             f"{info.get('engineInstanceId', '?')}).")
        try:
            from ..rollout import ReleaseRegistry

            reg = ReleaseRegistry(
                storage, info["engineId"],
                info.get("engineVersion", "1"),
                info.get("engineVariant", "engine.json"))
            reg.record("undeploy",
                       instance_id=info.get("engineInstanceId", ""),
                       actor="ptpu undeploy",
                       reason=f"stopped {args.ip}:{args.port}")
        except Exception as e:  # noqa: BLE001 — history is best-effort
            _err(f"release history write failed: {e}")
    else:
        _out(f"Undeployed engine server at {args.ip}:{args.port}.")
    return 0


def cmd_batchpredict(args, storage: Storage) -> int:
    from ..workflow.batch_predict import run_batch_predict

    variant = load_variant(args.engine_json)
    engine, engine_params = engine_from_variant(variant)
    ctx = _make_ctx(storage)
    n = run_batch_predict(
        ctx, engine, engine_params,
        input_path=args.input, output_path=args.output,
        engine_id=args.engine_id or variant.get("id", "default"),
        engine_version=args.engine_version or variant.get("version", "1"),
        engine_variant=args.engine_json)
    _out(f"Wrote {n} prediction(s) to {args.output}.")
    return 0


def cmd_eventserver(args, storage: Storage) -> int:
    from ..server.eventserver import build_app
    from ..server.http import AppServer, ssl_context_from

    ssl_ctx = ssl_context_from(args.cert or None, args.key or None)
    server = AppServer(build_app(storage, stats=args.stats),
                       host=args.ip, port=args.port, ssl_context=ssl_ctx)
    scheme = "https" if ssl_ctx else "http"
    _out(f"Event Server is listening at {scheme}://{args.ip}:{server.port}.")
    if not args.stats:
        _out("Per-app /stats.json is OFF (enable with --stats); "
             "aggregate telemetry is always on at /metrics.")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        _out("Shutting down.")
    return 0


def cmd_storageserver(args, storage: Storage) -> int:
    """Serve this host's storage to REMOTE-backend clients (the pod
    topology: TPU hosts → storage server for events/metadata/models, no
    shared filesystem required)."""
    from ..server.http import AppServer, ssl_context_from
    from ..server.storageserver import build_app

    ssl_ctx = ssl_context_from(args.cert or None, args.key or None)
    server = AppServer(build_app(storage, secret=args.secret or None),
                       host=args.ip, port=args.port, ssl_context=ssl_ctx)
    scheme = "https" if ssl_ctx else "http"
    _out(f"Storage Server is listening at "
         f"{scheme}://{args.ip}:{server.port}. "
         f"Telemetry at /metrics.")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        _out("Shutting down.")
    return 0


def cmd_adminserver(args, storage: Storage) -> int:
    from ..server.adminserver import create_admin_server
    from ..server.http import ssl_context_from

    ssl_ctx = ssl_context_from(args.cert or None, args.key or None)
    server = create_admin_server(
        storage, host=args.ip, port=args.port,
        accesskey=args.accesskey or None, ssl_context=ssl_ctx)
    scheme = "https" if ssl_ctx else "http"
    _out(f"Admin server is listening at {scheme}://{args.ip}:{server.port}.")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        _out("Shutting down.")
    return 0


def cmd_dashboard(args, storage: Storage) -> int:
    from ..server.dashboard import create_dashboard
    from ..server.http import ssl_context_from

    ssl_ctx = ssl_context_from(args.cert or None, args.key or None)
    server = create_dashboard(
        storage, host=args.ip, port=args.port,
        accesskey=args.accesskey or None, ssl_context=ssl_ctx)
    scheme = "https" if ssl_ctx else "http"
    _out(f"Dashboard is listening at {scheme}://{args.ip}:{server.port}.")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        _out("Shutting down.")
    return 0


#: servers `start-all` supervises: name → (default port, needs_secret)
_START_ALL = {
    "eventserver": (7070, False),
    "adminserver": (7071, False),
    "dashboard": (9000, False),
    "storageserver": (7077, True),
}


def _pid_dir(args) -> str:
    d = os.path.expanduser(getattr(args, "pid_dir", "") or
                           os.environ.get("PIO_PID_DIR", "~/.ptpu"))
    os.makedirs(d, exist_ok=True)
    return d


def _pid_alive(pid: int) -> bool:
    # if the process is OUR child, reap a potential zombie first —
    # kill(pid, 0) succeeds on zombies, which would read as "alive"
    # forever when start-all and stop-all share a process (tests,
    # embedding); standalone CLIs never are the parent and the
    # waitpid is a cheap no-op error
    try:
        os.waitpid(pid, os.WNOHANG)
    except ChildProcessError:
        pass
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def cmd_start_all(args, storage: Storage) -> int:
    """``ptpu start-all`` — the ``bin/pio-start-all`` role
    (``/root/reference/bin/pio-start-all:1-30``) for bare-metal
    operators: spawn the long-running servers as daemons with pidfiles
    and per-server logs, wait for each to answer its port, report.
    Docker users get the same topology from docker/docker-compose.yml;
    this is the no-docker path."""
    import socket
    import subprocess

    d = _pid_dir(args)
    names = ["eventserver", "adminserver", "dashboard"]
    if args.with_storageserver:
        names.insert(0, "storageserver")
    started, failed = [], []
    ports = {"eventserver": args.event_port,
             "adminserver": args.admin_port,
             "dashboard": args.dash_port,
             "storageserver": args.storage_port}
    for name in names:
        port = ports[name] or _START_ALL[name][0]
        pidfile = os.path.join(d, f"{name}.pid")
        if os.path.exists(pidfile):
            try:
                old = int(open(pidfile).read().strip())
            except ValueError:
                old = -1
            if old > 0 and _pid_alive(old):
                _err(f"{name} already running (pid {old}, {pidfile}); "
                     f"run stop-all first")
                failed.append(name)
                continue
            os.unlink(pidfile)  # stale pidfile from a dead process
        cmd = [sys.executable, "-m", "predictionio_tpu.cli", name,
               "--ip", args.ip, "--port", str(port)]
        if name == "storageserver" and args.storage_secret:
            cmd += ["--secret", args.storage_secret]
        log_path = os.path.join(d, f"{name}.log")
        with open(log_path, "ab") as log_f:
            proc = subprocess.Popen(
                cmd, stdout=log_f, stderr=subprocess.STDOUT,
                start_new_session=True)  # survives this CLI's exit
        with open(pidfile, "w") as f:
            f.write(str(proc.pid))
        # wait for the port to answer (the server binds before serving)
        deadline = time.monotonic() + args.start_timeout
        up = False
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                break  # died during startup; log has the reason
            try:
                with socket.create_connection(
                        ("127.0.0.1" if args.ip == "0.0.0.0"
                         else args.ip, port), timeout=1.0):
                    up = True
                    break
            except OSError:
                time.sleep(0.1)
        if up:
            # the port answering is not proof OUR child owns it: a
            # foreign listener (port collision) answers while the
            # child dies on bind-EADDRINUSE a beat later
            time.sleep(0.3)
            if proc.poll() is not None:
                up = False
        if up:
            _out(f"{name}: up on port {port} (pid {proc.pid}, "
                 f"log {log_path})")
            started.append(name)
        else:
            _err(f"{name}: failed to come up on port {port} within "
                 f"{args.start_timeout}s — see {log_path}")
            if proc.poll() is None:
                # escalate and CONFIRM death before dropping the
                # pidfile: a server stuck in native init ignores
                # SIGTERM and would otherwise survive as an orphan
                # no stop-all can find
                proc.terminate()
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    try:
                        proc.wait(timeout=5)
                    except subprocess.TimeoutExpired:
                        _err(f"{name}: pid {proc.pid} survived "
                             f"SIGKILL; keeping pidfile for stop-all")
                        failed.append(name)
                        continue
            os.unlink(pidfile)
            failed.append(name)
    if failed:
        return 1
    _out(f"All servers up ({', '.join(started)}). "
         f"`ptpu stop-all` stops them.")
    return 0


def cmd_stop_all(args, storage: Storage) -> int:
    """``ptpu stop-all`` — SIGTERM every pidfile'd server, escalate to
    SIGKILL after a grace period, clean up pidfiles (the
    ``bin/pio-stop-all`` role)."""
    import signal as _signal

    d = _pid_dir(args)
    stopped = 0
    for name in _START_ALL:
        pidfile = os.path.join(d, f"{name}.pid")
        if not os.path.exists(pidfile):
            continue
        try:
            pid = int(open(pidfile).read().strip())
        except ValueError:
            os.unlink(pidfile)
            continue
        if _pid_alive(pid):
            try:
                os.kill(pid, _signal.SIGTERM)
            except ProcessLookupError:
                # exited between the aliveness check and the signal —
                # already what we wanted; fall through to cleanup
                pass
            except PermissionError:
                # we spawned our servers as this user; a pid we cannot
                # signal was recycled by someone else's process after a
                # crash/reboot — stale pidfile, nothing of ours to stop
                _out(f"{name}: pid {pid} now belongs to a foreign "
                     f"process (recycled after crash?); dropping "
                     f"stale pidfile")
                os.unlink(pidfile)
                continue
            deadline = time.monotonic() + args.stop_timeout
            while time.monotonic() < deadline and _pid_alive(pid):
                time.sleep(0.1)
            if _pid_alive(pid):
                _err(f"{name} (pid {pid}) ignored SIGTERM; killing")
                try:
                    os.kill(pid, _signal.SIGKILL)
                except ProcessLookupError:
                    pass  # exited in the TERM→KILL window
                kill_deadline = time.monotonic() + 10.0
                while _pid_alive(pid) and \
                        time.monotonic() < kill_deadline:
                    time.sleep(0.05)
                if _pid_alive(pid):
                    _err(f"{name} (pid {pid}) survived SIGKILL "
                         f"(unreaped?); leaving pidfile")
                    continue
            _out(f"{name}: stopped (pid {pid})")
            stopped += 1
        else:
            _out(f"{name}: not running (stale pidfile)")
        os.unlink(pidfile)
    if stopped == 0:
        _out("Nothing to stop.")
    return 0


def cmd_status(args, storage: Storage) -> int:
    """``pio status`` (``commands/Management.scala:99``): environment +
    storage smoke check + the active release per tracked engine (not
    just process liveness — the RELEASE is what serves traffic)."""
    _out(f"PredictionIO-TPU {__version__}")
    try:
        import jax
        _out(f"JAX {jax.__version__}; devices: "
             f"{[str(d) for d in jax.devices()]}")
    except Exception as e:  # noqa: BLE001 — report, don't crash status
        _err(f"JAX initialization failed: {e}")
        return 1
    try:
        storage.verify_all_data_objects()
        _out("Storage: all data objects verified.")
    except Exception as e:  # noqa: BLE001
        _err(f"Storage check failed: {e}")
        return 1
    try:
        from ..rollout import ReleaseRegistry

        tracked = ReleaseRegistry.list_tracked(storage)
    except Exception as e:  # noqa: BLE001 — release state is advisory
        _err(f"release registry read failed: {e}")
        tracked = []
    for engine_id, engine_version, engine_variant in tracked:
        from ..rollout import ReleaseRegistry

        st = ReleaseRegistry(storage, engine_id, engine_version,
                             engine_variant).state()
        line = (f"Release [{engine_id} v{engine_version}]: "
                f"stable={st.get('stable') or '(none)'}")
        if st.get("pinned"):
            line += f" pinned={st['pinned']}"
        if st.get("candidate"):
            line += (f" candidate={st['candidate']} "
                     f"({st.get('candidateMode')} at "
                     f"{float(st.get('fraction') or 0) * 100:.0f}%)")
        _out(line)
    if getattr(args, "ip", ""):
        # model-lineage satellite (ISSUE 10): when pointed at a live
        # engine server, show what blend of batch + stream is actually
        # serving — base retrain, fold-in generations, staleness
        try:
            status_payload = _server_call(args, "/status.json")
        except Exception as e:  # noqa: BLE001 — liveness is optional
            _err(f"engine server at {args.ip}:{args.port} unreachable "
                 f"({e}); skipping lineage")
            status_payload = None
        lin = (status_payload or {}).get("lineage") or {}
        if lin:
            line = (f"Serving [{status_payload.get('engineId', '?')}]: "
                    f"base {lin.get('baseInstanceId', '?')} "
                    f"+{lin.get('incrementalGeneration', 0)} fold-ins "
                    f"({lin.get('incrementalRows', 0)} rows), "
                    f"staleness {lin.get('stalenessSec', '?')}s"
                    + (", stream live" if lin.get("streaming") else ""))
            _out(line)
    _out("(sleeping 0 seconds) Your system is all ready to go.")
    return 0


def cmd_release(args, storage: Storage) -> int:
    """``ptpu release`` — the progressive-delivery console (ISSUE 3):
    list/show release state and history from storage; pin releases;
    drive a running engine server's canary/promote/rollback/status
    over its control routes."""
    from ..rollout import ReleaseRegistry
    from ..rollout.splitter import parse_fraction

    sub = args.release_command

    if sub == "list":
        tracked = ReleaseRegistry.list_tracked(storage)
        if not tracked:
            _out("No releases recorded yet (deploy to create one).")
            return 0
        for engine_id, engine_version, engine_variant in sorted(tracked):
            st = ReleaseRegistry(storage, engine_id, engine_version,
                                 engine_variant).state()
            _out(f"{engine_id} v{engine_version} ({engine_variant}): "
                 f"stable={st.get('stable') or '(none)'} "
                 f"pinned={st.get('pinned') or '-'} "
                 f"candidate={st.get('candidate') or '-'}")
        return 0

    reg = ReleaseRegistry(storage, args.engine_id or "default",
                          args.engine_version or "1",
                          args.engine_json)

    if sub == "show":
        payload = reg.to_json(history_limit=args.limit)
        _out(json.dumps(payload, indent=2))
        return 0

    if sub == "pin":
        if args.clear:
            reg.unpin(actor="ptpu release", reason=args.reason)
            _out("Unpinned; deploy/reload bind the latest COMPLETED "
                 "instance again.")
            return 0
        if not args.instance_id:
            _err("instance_id required (or --clear).")
            return 1
        try:
            reg.pin(args.instance_id, actor="ptpu release",
                    reason=args.reason)
        except ValueError as e:
            _err(str(e))
            return 1
        _out(f"Pinned release {args.instance_id}; deploy/reload now "
             f"bind it (POST /reload to apply on a live server).")
        return 0

    if sub == "status":
        try:
            payload = _server_call(args, "/release.json")
        except Exception as e:  # noqa: BLE001 — fall back to storage
            _err(f"engine server at {args.ip}:{args.port} unreachable "
                 f"({e}); showing storage state")
            _out(json.dumps(reg.to_json(history_limit=10), indent=2))
            return 0
        _out(json.dumps(payload, indent=2))
        return 0

    if sub == "canary":
        try:
            fraction = (parse_fraction(args.fraction)
                        if args.fraction else None)
        except ValueError as e:
            _err(str(e))
            return 1
        body = {"instanceId": args.instance_id, "shadow": args.shadow,
                "actor": "ptpu release", "reason": args.reason}
        if fraction is not None:
            body["fraction"] = fraction
        try:
            resp = _server_call(args, "/release/canary", method="POST",
                                body=body)
        except Exception as e:  # noqa: BLE001 — report, don't traceback
            _err(f"canary start failed: {_http_err_detail(e)}")
            return 1
        ro = (resp or {}).get("rollout") or {}
        _out(f"{'Shadow' if args.shadow else 'Canary'} rollout of "
             f"{args.instance_id} started at "
             f"{float(ro.get('fraction') or 0) * 100:.0f}% "
             f"(watch: ptpu release status).")
        return 0

    if sub in ("promote", "rollback"):
        try:
            resp = _server_call(args, f"/release/{sub}", method="POST",
                                body={"reason": args.reason})
        except Exception as e:  # noqa: BLE001 — report, don't traceback
            _err(f"{sub} failed: {_http_err_detail(e)}")
            return 1
        _out(f"{resp.get('message', 'OK')} Serving instance: "
             f"{resp.get('engineInstanceId', '?')}")
        return 0

    _err(f"Unknown release subcommand {sub!r}")
    return 1


def cmd_cache(args, storage: Storage) -> int:
    """``ptpu cache`` — operate a running engine server's serving
    cache hierarchy (ISSUE 4): per-tier stats, operator flush."""
    sub = args.cache_command
    if sub == "stats":
        try:
            payload = _server_call(args, "/cache.json")
        except Exception as e:  # noqa: BLE001 — report, don't traceback
            _err(f"engine server at {args.ip}:{args.port} unreachable: "
                 f"{_http_err_detail(e)}")
            return 1
        if not (payload or {}).get("enabled"):
            _out("Serving cache is OFF on this server "
                 "(deploy with --cache).")
            return 0
        _out(json.dumps(payload, indent=2))
        tiers = payload.get("tiers") or {}
        for name, t in tiers.items():
            total = t.get("hits", 0) + t.get("misses", 0)
            _out(f"{name}: {t.get('entries', 0)} entries, "
                 f"{t.get('hitRatio', 0) * 100:.1f}% hit ratio over "
                 f"{total} lookups, {t.get('invalidations', 0)} "
                 f"invalidations")
        return 0
    if sub == "flush":
        try:
            payload = _server_call(args, "/cache/flush", method="POST")
        except Exception as e:  # noqa: BLE001 — report, don't traceback
            _err(f"cache flush failed: {_http_err_detail(e)}")
            return 1
        removed = (payload or {}).get("removed") or {}
        _out("Flushed: " + ", ".join(f"{k}={v}"
                                     for k, v in removed.items()))
        return 0
    _err(f"Unknown cache subcommand {sub!r}")
    return 1


def cmd_stream(args, storage: Storage) -> int:
    """``ptpu stream`` — operate a running engine server's streaming
    incremental trainer (ISSUE 10, docs/streaming.md): attach, stop,
    and inspect the event→model loop."""
    sub = args.stream_command
    if sub == "status":
        try:
            payload = _server_call(args, "/stream.json")
        except Exception as e:  # noqa: BLE001 — report, don't traceback
            _err(f"engine server at {args.ip}:{args.port} unreachable: "
                 f"{_http_err_detail(e)}")
            return 1
        _out(json.dumps(payload, indent=2))
        lin = (payload or {}).get("lineage") or {}
        if lin:
            line = (f"serving: base {lin.get('baseInstanceId', '?')} "
                    f"+{lin.get('incrementalGeneration', 0)} fold-ins "
                    f"({lin.get('incrementalRows', 0)} rows), "
                    f"staleness {lin.get('stalenessSec', '?')}s")
            _out(line)
        if not (payload or {}).get("running"):
            _out("Streaming trainer is OFF (ptpu stream start --app "
                 "<app>, or deploy with --stream).")
        return 0
    if sub == "start":
        body = {}
        if args.app:
            body["appName"] = args.app
        if args.channel:
            body["channelName"] = args.channel
        if args.consumer:
            body["consumer"] = args.consumer
        if args.interval_ms is not None:
            body["intervalMs"] = args.interval_ms
        if args.max_events is not None:
            body["maxEvents"] = args.max_events
        if args.drift_threshold is not None:
            body["driftThreshold"] = args.drift_threshold
        if args.canary_probes is not None:
            body["canaryProbes"] = args.canary_probes
        try:
            resp = _server_call(args, "/stream/start", method="POST",
                                body=body)
        except Exception as e:  # noqa: BLE001 — report, don't traceback
            _err(f"stream start failed: {_http_err_detail(e)}")
            return 1
        st = (resp or {}).get("stream") or {}
        _out(f"Streaming trainer started (app "
             f"{st.get('appName', '?')}, consumer "
             f"{st.get('consumer', '?')}, interval "
             f"{st.get('intervalMs', '?')}ms). Watch: ptpu stream "
             f"status.")
        return 0
    if sub == "stop":
        try:
            resp = _server_call(args, "/stream/stop", method="POST")
        except Exception as e:  # noqa: BLE001 — report, don't traceback
            _err(f"stream stop failed: {_http_err_detail(e)}")
            return 1
        _out((resp or {}).get("message", "Stopped."))
        _out("The durable cursor keeps its position; a later start "
             "with the same consumer resumes exactly there.")
        return 0
    _err(f"Unknown stream subcommand {sub!r}")
    return 1


def _print_slo_payload(payload: Optional[dict]) -> int:
    """One line per spec from a ``/slo.json`` body (shared by ``ptpu
    slo status`` and ``ptpu fleet slo``); exit 1 while burning."""
    p = payload or {}
    if not p.get("enabled", False):
        _out("SLO engine is disabled on this server "
             f"({p.get('hint', '')})")
        return 0
    burning = p.get("burning") or []
    for sp in p.get("specs") or []:
        budget = sp.get("budgetRemaining")
        bits = [f"{sp['name']:<28} {sp['state']:<18}"]
        for key, label in (("burnFast", "fast"),
                           ("burnSlow", "slow")):
            v = sp.get(key)
            bits.append(f"burn[{label}] "
                        + (f"{v:6.2f}x" if v is not None
                           else "     ?"))
        bits.append("budget "
                    + (f"{budget * 100:6.1f}%" if budget is not None
                       else "     ?"))
        bits.append(f"violations {sp.get('violations', 0)}")
        _out("  ".join(bits))
    _out(f"{len(p.get('specs') or [])} spec(s), "
         + (f"BURNING: {', '.join(burning)}" if burning
            else "none burning")
         + f" ({p.get('ticks', 0)} evaluation ticks)")
    return 1 if burning else 0


def cmd_slo(args, storage: Storage) -> int:
    """``ptpu slo`` (ISSUE 15, docs/slo.md):

    - ``status`` — a running server's live burn rates / budgets
      (``GET /slo.json``), one line per spec;
    - ``check`` — the CI capacity gate: diff a ``load_harness``
      ``CAPACITY.json`` against the committed spec file with ratchet
      semantics (regressions fail naming the spec, the measurement
      window, and the measured value; ``--update`` tightens the
      committed gates toward a better run, never loosens them).
    """
    if args.slo_command == "status":
        try:
            payload = _server_call(args, "/slo.json")
        except Exception as e:  # noqa: BLE001 — report, don't traceback
            _err(f"server at {args.ip}:{args.port} unreachable: "
                 f"{_http_err_detail(e)}")
            return 1
        return _print_slo_payload(payload)
    # check: gate CAPACITY.json against the committed spec file
    from ..slo import (
        gate_capacity,
        load_specs,
        ratchet_gates,
        write_gates,
    )

    try:
        with open(args.capacity, encoding="utf-8") as f:
            capacity = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        _err(f"cannot read capacity model {args.capacity}: {e}")
        return 1
    try:
        _specs, gates = load_specs(args.specs)
    except (OSError, ValueError) as e:
        _err(f"cannot read SLO spec file {args.specs}: {e}")
        return 1
    if not gates:
        _err(f"{args.specs} commits no capacity gates; add a "
             f"'capacity' section (docs/slo.md)")
        return 1
    failures = gate_capacity(capacity, gates)
    for line in failures:
        _err(f"FAIL {line}")
    if failures:
        _err(f"{len(failures)} capacity regression(s) vs {args.specs} "
             f"— fix the regression or, for an accepted trade-off, "
             f"loosen the committed gate in an explicit commit")
        return 1
    n_checked = sum(len(g) for g in gates.values())
    _out(f"capacity gate PASS: {n_checked} committed limit(s) over "
         f"{len(gates)} config(s) hold for {args.capacity}")
    if args.update:
        new_gates, changes = ratchet_gates(capacity, gates)
        if changes:
            write_gates(args.specs, new_gates)
            for c in changes:
                _out(f"ratchet {c}")
            _out(f"tightened {len(changes)} gate(s) in {args.specs} — "
                 f"commit the file")
        else:
            _out("no gate beat its committed value; nothing to ratchet")
    return 0


def cmd_trace(args, storage: Storage) -> int:
    """``ptpu trace`` — read a running server's tail-sampled flight
    recorder (ISSUE 12, docs/tracing.md): recorder status, the N
    slowest retained traces, or one trace exported as Chrome/Perfetto
    trace-event JSON (load the file at ui.perfetto.dev)."""
    try:
        if args.id:
            payload = _server_call(args, f"/trace.json?id={args.id}")
        elif args.slowest is not None:
            payload = _server_call(
                args, f"/trace.json?slowest={args.slowest}")
        else:
            payload = _server_call(args, "/trace.json")
    except Exception as e:  # noqa: BLE001 — report, don't traceback
        _err(f"server at {args.ip}:{args.port} unreachable: "
             f"{_http_err_detail(e)}")
        return 1
    if args.id:
        out_path = args.output or f"trace-{args.id[:12]}.json"
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(payload, f)
        n = len((payload or {}).get("traceEvents") or [])
        _out(f"Wrote {n} trace events to {out_path} — load it at "
             f"https://ui.perfetto.dev (or chrome://tracing).")
        return 0
    if args.slowest is not None:
        traces = (payload or {}).get("traces") or []
        if not traces:
            _out("No retained traces yet (only slow / errored / "
                 "deadline-503'd / fault-injected requests are kept).")
            return 0
        for t in traces:
            _out(f"{t.get('traceId')}  {t.get('durationMs', '?')}ms  "
                 f"status={t.get('status')}  "
                 f"reason={t.get('reason')}  {t.get('name', '')}")
        _out(f"Export one: ptpu trace --id {traces[0]['traceId']}")
        return 0
    _out(json.dumps(payload, indent=2))
    p = payload or {}
    _out(f"flight recorder: {p.get('retained', 0)}/"
         f"{p.get('ringCapacity', '?')} retained of "
         f"{p.get('requests', 0)} traced requests"
         + (f", slow ≥ {p['slowThresholdMs']}ms"
            if p.get("slowThresholdMs") is not None else ""))
    return 0


def _http_err_detail(e: Exception) -> str:
    """Surface the server's JSON error message instead of a bare
    'HTTP Error 409'."""
    import urllib.error

    if isinstance(e, urllib.error.HTTPError):
        try:
            body = json.loads(e.read() or b"{}")
            return f"{e.code}: {body.get('message', '')}"
        except Exception:  # noqa: BLE001 — fall back to the bare error
            return str(e)
    return str(e)


def cmd_fleet(args) -> int:
    """``ptpu fleet`` (ISSUE 17, docs/fleet.md) — the fleet
    observability plane:

    - ``serve`` — run the aggregator: scrape every ``--replicas``
      member's ``/metrics.json``, merge exactly (counters sum,
      histograms pool buckets, gauges gain replica labels + rollups),
      evaluate fleet-scoped SLOs over the MERGED series, and serve
      the fleet surface (``/``, ``/fleet.json``, ``/metrics``,
      ``/slo.json``, ``/trace.json``, ``/hotkeys.json``);
    - ``status`` — per-replica liveness/lag/flags + fleet headroom
      from a running aggregator (exit 1 when replicas are down or a
      fleet SLO burns);
    - ``slo`` — the fleet SLO engine's burn rates (merged-series
      verdicts, one line per spec);
    - ``trace`` — cross-replica flight-recorder lookup: ``--id``
      fans out to every replica and exports the hit, ``--slowest N``
      merges fleet-wide;
    - ``hotkeys`` — the fleet-wide Space-Saving top-K (and each
      replica's own view);
    - ``route`` — the query router's view (ISSUE 18): ring
      membership, per-backend state, where a ``--key`` would land;
    - ``scale`` — hand the autoscaler a manual replica-count target
      (clamped to its policy bounds, logged in the decision log).

    Pure HTTP: needs neither storage nor jax.
    """
    if args.fleet_command == "serve":
        from ..fleet import FleetConfig, create_fleet_server
        from ..server.http import ssl_context_from

        cfg = FleetConfig(
            replicas=[r.strip() for r in args.replicas.split(",")
                      if r.strip()],
            scrape_interval_sec=args.scrape_interval_ms / 1000.0,
            stale_after_sec=(args.stale_after_ms / 1000.0
                             if args.stale_after_ms else None),
            slo_specs=args.slo_specs or None,
            slo_interval_sec=args.slo_interval_ms / 1000.0,
            capacity_path=args.capacity or None,
            hot_keys_k=args.hot_keys_k,
            timeout_sec=args.timeout_sec,
            accesskey=args.accesskey or None)
        ssl_ctx = ssl_context_from(args.cert or None, args.key or None)
        agg, server = create_fleet_server(cfg, host=args.ip,
                                          port=args.port,
                                          ssl_context=ssl_ctx)
        scheme = "https" if ssl_ctx else "http"
        _out(f"Fleet aggregator live at {scheme}://{args.ip}:"
             f"{server.port} over {len(cfg.replicas)} replica(s).")
        _out(f"Merged telemetry: {scheme}://{args.ip}:{server.port}"
             f"/metrics · /fleet.json · /slo.json · /trace.json · "
             f"/hotkeys.json")
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            _out("Shutting down.")
            agg.stop()
        return 0
    try:
        if args.fleet_command == "status":
            payload = _server_call(args, "/fleet.json") or {}
        elif args.fleet_command == "slo":
            return _print_slo_payload(_server_call(args, "/slo.json"))
        elif args.fleet_command == "hotkeys":
            payload = _server_call(
                args, f"/hotkeys.json?n={args.top}") or {}
        elif args.fleet_command == "route":
            import urllib.parse as _up

            path = "/route.json"
            if args.key:
                path += "?key=" + _up.quote(args.key)
            payload = _server_call(args, path) or {}
        elif args.fleet_command == "scale":
            import urllib.parse as _up

            path = f"/scale?to={int(args.to)}"
            if args.reason:
                path += "&reason=" + _up.quote(args.reason)
            payload = _server_call(args, path, method="POST") or {}
        else:  # trace
            if args.id:
                payload = _server_call(args,
                                       f"/trace.json?id={args.id}")
            elif args.slowest is not None:
                payload = _server_call(
                    args, f"/trace.json?slowest={args.slowest}")
            else:
                payload = _server_call(args, "/trace.json")
    except Exception as e:  # noqa: BLE001 — report, don't traceback
        _err(f"fleet aggregator at {args.ip}:{args.port} unreachable: "
             f"{_http_err_detail(e)}")
        return 1
    if args.fleet_command == "status":
        # the autoscaler's decision log tells an INTENTIONAL exit
        # (scale-in terminate) from a corpse: a replica it removed —
        # or one mid-drain — is not a failure and must not flip the
        # exit code (ISSUE 18 satellite)
        autoscale = payload.get("autoscale") or {}
        removed = set(autoscale.get("removed") or [])
        down = 0
        for r in payload.get("replicas") or []:
            up = r.get("up")
            lifecycle = r.get("lifecycle")
            if up:
                state = ("draining" if lifecycle == "draining"
                         else "up")
            elif (r.get("replica") in removed
                  or lifecycle == "draining"):
                state = "removed"   # scale-in, not an outage
            else:
                state = "DOWN"
                down += 1
            flags = []
            if r.get("degraded"):
                flags.append("DEGRADED")
            if r.get("nonfinite"):
                flags.append("NONFINITE")
            if r.get("sloBurning"):
                flags.append("burning:" + ",".join(r["sloBurning"]))
            age = r.get("lastScrapeAgeSec")
            _out(f"{r.get('replica', '?'):<24} "
                 f"{state:<9} "
                 f"age {age if age is not None else '?':>7}s  "
                 f"requests {r.get('requestCount') or 0:>8}  "
                 f"{' '.join(flags)}")
        headroom = payload.get("capacityHeadroom")
        burning = (payload.get("slo") or {}).get("burning") or []
        _out(f"{payload.get('replicasUp', 0)}/"
             f"{payload.get('replicasConfigured', 0)} replicas up, "
             f"qps {payload.get('qps', 0.0):.2f}, headroom "
             + (f"{headroom:.3f}" if headroom is not None else "?")
             + (f", fleet SLO BURNING: {', '.join(burning)}"
                if burning else ", fleet SLO ok")
             + f" ({payload.get('cycles', 0)} scrape cycles)")
        if autoscale.get("enabled"):
            decisions = autoscale.get("decisions") or []
            last = decisions[-1] if decisions else {}
            _out(f"autoscale: target {autoscale.get('target')}, "
                 f"{len(removed)} scaled-in, last decision "
                 f"{last.get('action', 'none')}"
                 + (f" ({last.get('reason')})"
                    if last.get("reason") else ""))
        return 1 if (down or burning) else 0
    if args.fleet_command == "hotkeys":
        for k in payload.get("fleet") or []:
            _out(f"{k['key']:<32} {k['count']:>12.0f} "
                 f"(±{k['error']:.0f})")
        if not payload.get("fleet"):
            _out("No hot keys observed yet (the sketch fills from "
                 "query-path entity ids).")
        return 0
    if args.fleet_command == "route":
        for b in payload.get("replicas") or []:
            _out(f"{b.get('replica', '?'):<24} "
                 f"{b.get('state', '?'):<9} "
                 f"inflight {b.get('inflight', 0):>4}  "
                 f"requests {b.get('requests', 0):>8}  "
                 f"failures {b.get('consecutiveFailures', 0)}")
        if args.key:
            _out(f"key {args.key!r} → {payload.get('affinity')} "
                 f"(preference: "
                 f"{', '.join(payload.get('preference') or [])})")
        ring = payload.get("ring") or {}
        _out(f"{len(payload.get('replicas') or [])} backend(s), "
             f"{ring.get('vnodes', '?')} vnodes each; retries "
             f"{payload.get('retries')}; spill "
             f"{(payload.get('spill') or {}).get('share')}")
        return 0
    if args.fleet_command == "scale":
        _out(f"requested {payload.get('requested')} → target "
             f"{payload.get('target')} (clamped to policy bounds); "
             f"the control loop converges on its next tick.")
        return 0
    # trace
    if args.id:
        trace = (payload or {}).get("trace")
        replica = (payload or {}).get("replica", "?")
        out_path = args.output or f"trace-{args.id[:12]}.json"
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(trace, f)
        n = len((trace or {}).get("traceEvents") or [])
        _out(f"Trace found on replica {replica}; wrote {n} trace "
             f"events to {out_path} — load it at "
             f"https://ui.perfetto.dev.")
        return 0
    if args.slowest is not None:
        traces = (payload or {}).get("traces") or []
        if not traces:
            _out("No retained traces anywhere in the fleet yet.")
            return 0
        for t in traces:
            _out(f"{t.get('traceId')}  {t.get('durationMs', '?')}ms  "
                 f"replica={t.get('replica')}  "
                 f"status={t.get('status')}  "
                 f"reason={t.get('reason')}  {t.get('name', '')}")
        _out(f"Export one: ptpu fleet trace --id "
             f"{traces[0]['traceId']} --port {args.port}")
        return 0
    _out(json.dumps(payload, indent=2))
    return 0


def cmd_export(args, storage: Storage) -> int:
    """``pio export`` (``tools/export/EventsToFile.scala``): events →
    JSON-lines file."""
    from ..data.storage.base import EventFilter

    a = storage.apps().get_by_name(args.app) if args.app else \
        storage.apps().get(args.appid)
    if a is None:
        _err("App does not exist. Aborting.")
        return 1
    channel_id = None
    if args.channel:
        ch = _find_channel(storage, a, args.channel)
        if ch is None:
            _err(f"Channel {args.channel} does not exist. Aborting.")
            return 1
        channel_id = ch.id
    n = 0
    with open(args.output, "w", encoding="utf-8") as f:
        for e in storage.events().find(a.id, channel_id, EventFilter()):
            f.write(json.dumps(e.to_json()) + "\n")
            n += 1
    _out(f"Exported {n} event(s) to {args.output}.")
    return 0


def cmd_import(args, storage: Storage) -> int:
    """``pio import`` (``tools/imprt/FileToEvents.scala``): JSON-lines →
    event store."""
    a = storage.apps().get_by_name(args.app) if args.app else \
        storage.apps().get(args.appid)
    if a is None:
        _err("App does not exist. Aborting.")
        return 1
    channel_id = None
    if args.channel:
        ch = _find_channel(storage, a, args.channel)
        if ch is None:
            _err(f"Channel {args.channel} does not exist. Aborting.")
            return 1
        channel_id = ch.id
    # import streams in chunks (a 20M-line file must not materialize
    # every Event at once), each committed all-or-nothing — backends
    # with a native bulk lane (segmentfs) override import_jsonl with a
    # one-pass C++ encode. A mid-file failure reports exactly which
    # durable prefix is committed instead of dying with a traceback
    # and an unknown amount of half-imported data.
    from ..data.storage.base import JsonlImportError

    chunk = int(os.environ.get("PIO_IMPORT_BATCH", "100000"))
    try:
        total = storage.events().import_jsonl(
            args.input, a.id, channel_id, chunk=chunk)
    except JsonlImportError as err:
        _err(f"Import failed near line {err.lineno}: {err.cause}")
        app_flag = f"--app {args.app}" if args.app \
            else f"--appid {args.appid}"
        _err(f"{err.committed_events} event(s) (input lines "
             f"1-{err.committed_lines}) are already committed. "
             f"Re-importing this file would DUPLICATE them — resume "
             f"with the remainder only, e.g.: "
             f"tail -n +{err.committed_lines + 1} {args.input} > rest."
             f"jsonl && ptpu import {app_flag} --input rest.jsonl "
             f"(or app data-delete to start over).")
        return 1
    except OSError as e:
        _err(f"Import failed: {e}")
        return 1
    _out(f"Imported {total} event(s).")
    # pay the one-time columnar-sidecar encode HERE (ingest already
    # parsed every byte) instead of surprising the first `ptpu train`
    # with it — measured 176s of a 299s first train at ML-20M scale
    t0 = time.monotonic()
    try:
        warmed = storage.events().warm_columnar(a.id, channel_id)
    except Exception as e:  # noqa: BLE001 — warm is advisory, never
        _err(f"columnar warm failed (first read will pay the "
             f"encode): {e}")
        warmed = False
    if warmed:
        _out(f"Columnar sidecar ready ({time.monotonic() - t0:.1f}s).")
    return 0


def artifact_root(arg: str = "") -> str:
    """Resolve the AOT artifact store root: explicit flag, then
    $PTPU_ARTIFACT_DIR, then ~/.ptpu/artifacts."""
    return (arg or os.environ.get("PTPU_ARTIFACT_DIR", "")
            or os.path.join(os.path.expanduser("~"), ".ptpu",
                            "artifacts"))


def cmd_build(args, storage: Storage) -> int:
    """No sbt here: 'build' verifies the engine variant is loadable
    (``commands/Engine.scala:66-139`` becomes an import check). With
    ``--aot`` (ISSUE 19) it additionally compiles the serving entry
    points for the latest COMPLETED instance and serializes the
    executables into the artifact store, so a matching deploy warms by
    loading them (docs/cold-start.md)."""
    variant = load_variant(args.engine_json)
    engine, engine_params = engine_from_variant(variant)
    n_algos = len(engine_params.algorithms)
    _out(f"Engine factory {variant.get('engineFactory')} loads OK "
         f"({n_algos} algorithm(s) configured).")
    if getattr(args, "aot", False):
        from ..server.engineserver import ServerConfig, build_artifacts

        ctx = _make_ctx(storage)
        config = ServerConfig(
            batching=args.batching,
            max_batch=args.max_batch,
            serving_mode=args.serving_mode,
            serving_quant=args.serving_quant)
        result = build_artifacts(
            ctx, engine, engine_params,
            artifact_root(args.artifact_dir),
            engine_id=args.engine_id or variant.get("id", "default"),
            engine_version=(args.engine_version
                            or variant.get("version", "1")),
            engine_variant=args.engine_json,
            config=config)
        _out(f"AOT artifacts: {result['entries']} serving "
             f"executable(s) for instance {result['instance']} in "
             f"{result['seconds']:.1f}s -> {result['path']}")
        _out(f"Deploy with --artifact-dir "
             f"{artifact_root(args.artifact_dir)} (and the same "
             f"serving flags) to warm from them.")
    _out("Build finished successfully.")
    return 0


def cmd_shell(args, storage: Storage) -> int:
    """Interactive shell with the framework preloaded
    (``bin/pio-shell`` role; pypio is native here)."""
    import code

    from ..controller.context import Context
    from ..data.store import EventStoreFacade
    from ..pypio import PEventStore

    ns = {
        "storage": storage,
        "event_store": EventStoreFacade(storage),
        "p_event_store": PEventStore(EventStoreFacade(storage)),
        "Context": Context,
    }
    banner = ("PredictionIO-TPU shell. Preloaded: storage, event_store, "
              "p_event_store, Context.")
    try:
        import IPython

        IPython.start_ipython(argv=[], user_ns=ns)
    except ImportError:
        code.interact(banner=banner, local=ns)
    return 0


def cmd_run(args, storage: Storage) -> int:
    """Run a user entry point with storage configured
    (``pio run`` / ``commands/Engine.scala:332``)."""
    from ..data.storage import registry as _registry
    from ..data.storage.registry import set_storage

    fn = load_engine_factory(args.target)
    if not callable(fn):
        raise SystemExit(f"{args.target!r} is not callable")
    prior = _registry._global
    set_storage(storage)
    try:
        result = fn(*args.args)
        if result is not None:
            _out(str(result))
        return 0
    finally:
        set_storage(prior)


def cmd_check(args) -> int:
    """``ptpu check`` — JAX-aware + concurrency + Pallas-kernel static
    analysis, interprocedural over the scanned set (pure AST, no
    jax/storage import: safe on any host, fast enough for a pre-commit
    hook). Non-zero exit on findings — or, with ``--baseline``, on
    findings NOT in the baseline (which only ever ratchets down; see
    --baseline-grow). ``--format json|sarif`` for machines (sarif
    feeds GitHub code-scanning PR annotations, interprocedural call
    chains as relatedLocations); see docs/static-analysis.md."""
    from ..analysis import (
        RULES,
        findings_to_json,
        findings_to_sarif,
        load_baseline,
        new_findings,
        run_check,
        shrinkable_entries,
        write_baseline,
    )

    if args.list_rules:
        for name, rule in sorted(RULES.items()):
            _out(f"{name}: {rule.description}")
        return 0
    try:
        findings = run_check(args.paths or ["predictionio_tpu"],
                             rule_names=args.rule or None)
    except ValueError as e:
        _err(str(e))
        return 2
    if args.write_baseline:
        if not args.baseline:
            _err("--write-baseline requires --baseline FILE")
            return 2
        cap = None
        if not args.baseline_grow and os.path.exists(args.baseline):
            try:
                cap = load_baseline(args.baseline)
            except (OSError, ValueError, KeyError, TypeError) as e:
                _err(f"ptpu check: cannot read baseline: {e}")
                return 2
        n = write_baseline(args.baseline, findings, cap=cap)
        _err(f"ptpu check: wrote {n} baseline entr"
             f"{'y' if n == 1 else 'ies'} "
             f"({len(findings)} finding(s)) to {args.baseline}"
             f"{' (ratchet: shrink-only)' if cap is not None else ''}.")
        if cap is not None:
            overflow = new_findings(findings, cap)
            if overflow:
                _err(f"ptpu check: {len(overflow)} finding(s) exceed "
                     f"the recorded baseline and were NOT absorbed "
                     f"(the baseline only ratchets down; fix them or "
                     f"re-record deliberately with --baseline-grow):")
                for f in overflow:
                    _err(f"  {f.format()}")
                return 1
        return 0
    gating = findings
    baselined = 0
    if args.baseline:
        try:
            baseline = load_baseline(args.baseline)
        except (OSError, ValueError, KeyError, TypeError) as e:
            _err(f"ptpu check: cannot read baseline: {e}")
            return 2
        gating = new_findings(findings, baseline)
        baselined = len(findings) - len(gating)
        shrinkable = shrinkable_entries(findings, baseline)
        if shrinkable:
            _err(f"ptpu check: {len(shrinkable)} baseline entr"
                 f"{'y is' if len(shrinkable) == 1 else 'ies are'} "
                 f"no longer fully reproduced — the baseline can "
                 f"ratchet down (re-run with --write-baseline):")
            for (path, rule, _msg), rec, act in shrinkable:
                _err(f"  {path}: {rule}: recorded {rec}, found {act}")
    if args.format == "json":
        _out(findings_to_json(gating))
    elif args.format == "sarif":
        _out(findings_to_sarif(gating, RULES))
    else:
        for f in gating:
            _out(f.format())
    suffix = (f" ({baselined} baselined finding(s) not counted)"
              if baselined else "")
    if gating:
        _err(f"ptpu check: {len(gating)} "
             f"{'new ' if args.baseline else ''}finding(s){suffix}. "
             f"Fix them or suppress with "
             f"'# ptpu: allow[rule] — justification'.")
        return 1
    if args.format == "text":
        _out(f"ptpu check: clean.{suffix}")
    return 0


def cmd_audit_hlo(args) -> int:
    """``ptpu audit-hlo`` — compile the registered SPMD entry points
    on a forced 8-device CPU mesh, parse the optimized HLO for
    collective ops + temp allocations, and gate against the committed
    golden manifest (``analysis/hlo_baseline.json``) with the same
    ratchet semantics as ``ptpu check --baseline``. The static
    sharding rules catch spec disagreements the AST can see; this
    catches the collectives only XLA sees. Non-zero exit on new
    collectives / grown temps (see --baseline-grow);
    docs/parallelism.md has the diff-reading runbook."""
    from ..analysis import hlo_audit as ha

    if args.list_entries:
        for name, (_b, desc) in ha.ENTRY_POINTS.items():
            _out(f"{name}: {desc}")
        return 0
    try:
        manifest = ha.run_audit(args.entry or None)
    except ha.AuditError as e:
        _err(f"ptpu audit-hlo: {e}")
        return 2
    baseline_path = args.baseline or ha.DEFAULT_BASELINE
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(manifest, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.write_baseline:
        cap = None
        if not args.baseline_grow and os.path.exists(baseline_path):
            try:
                cap = ha.load_manifest(baseline_path)
            except (OSError, ValueError) as e:
                _err(f"ptpu audit-hlo: cannot read baseline: {e}")
                return 2
        ha.write_manifest(baseline_path, manifest, cap=cap)
        _err(f"ptpu audit-hlo: wrote "
             f"{len(manifest['entries'])} entry point(s) to "
             f"{baseline_path}"
             f"{' (ratchet: shrink-only)' if cap is not None else ''}.")
        if cap is not None:
            violations, _ = ha.diff_manifests(manifest, cap)
            if violations:
                _err(f"ptpu audit-hlo: {len(violations)} regression(s) "
                     f"were NOT absorbed (the baseline only ratchets "
                     f"down; fix them or re-record deliberately with "
                     f"--baseline-grow):")
                for v in violations:
                    _err(f"  {v}")
                return 1
        return 0
    if args.format == "json":
        _out(json.dumps(manifest, indent=2, sort_keys=True))
    else:
        _out(ha.format_text(manifest))
    if not os.path.exists(baseline_path):
        _err(f"ptpu audit-hlo: no baseline at {baseline_path} — "
             f"record one with --write-baseline (gate skipped).")
        return 0
    try:
        baseline = ha.load_manifest(baseline_path)
    except (OSError, ValueError) as e:
        _err(f"ptpu audit-hlo: cannot read baseline: {e}")
        return 2
    if args.entry:
        # a subset run gates only the audited entries — the others are
        # not "no longer reproduced", they were not compiled
        keep = set(args.entry)
        baseline = {**baseline,
                    "entries": {k: v
                                for k, v in baseline["entries"].items()
                                if k in keep}}
    violations, shrinkable = ha.diff_manifests(manifest, baseline)
    if shrinkable:
        _err(f"ptpu audit-hlo: {len(shrinkable)} baseline entr"
             f"{'y is' if len(shrinkable) == 1 else 'ies are'} no "
             f"longer fully reproduced — ratchet down with "
             f"--write-baseline:")
        for s in shrinkable:
            _err(f"  {s}")
    if violations:
        _err(f"ptpu audit-hlo: {len(violations)} collective/temp "
             f"regression(s) vs {baseline_path}:")
        for v in violations:
            _err(f"  {v}")
        return 1
    _err("ptpu audit-hlo: compiled collectives match the golden "
         "manifest.")
    return 0


def cmd_audit_numerics(args) -> int:
    """``ptpu audit-numerics`` — abstract-interpret the registered
    numeric entry points (a jaxpr walk, no device execution), extract
    the per-entry dtype census (op counts, cast inventory,
    accumulation dtypes, bytes by dtype) and gate against the
    committed golden manifest (``analysis/numerics_baseline.json``)
    with the same ratchet semantics as ``audit-hlo``. The static
    dtype-flow rules catch the narrowings the AST can see; this
    catches the ones only the traced program sees. Non-zero exit on
    new casts / narrowed accumulators / grown bytes (see
    --baseline-grow); docs/static-analysis.md has the diff-reading
    runbook."""
    from ..analysis import numerics_audit as na

    if args.list_entries:
        for name, (_b, desc) in na.ENTRY_POINTS.items():
            _out(f"{name}: {desc}")
        return 0
    try:
        manifest = na.run_audit(args.entry or None)
    except na.AuditError as e:
        _err(f"ptpu audit-numerics: {e}")
        return 2
    baseline_path = args.baseline or na.DEFAULT_BASELINE
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(manifest, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.write_baseline:
        cap = None
        if not args.baseline_grow and os.path.exists(baseline_path):
            try:
                cap = na.load_manifest(baseline_path)
            except (OSError, ValueError) as e:
                _err(f"ptpu audit-numerics: cannot read baseline: {e}")
                return 2
        na.write_manifest(baseline_path, manifest, cap=cap)
        _err(f"ptpu audit-numerics: wrote "
             f"{len(manifest['entries'])} entry point(s) to "
             f"{baseline_path}"
             f"{' (ratchet: shrink-only)' if cap is not None else ''}.")
        if cap is not None:
            violations, _ = na.diff_manifests(manifest, cap)
            if violations:
                _err(f"ptpu audit-numerics: {len(violations)} "
                     f"regression(s) were NOT absorbed (the baseline "
                     f"only ratchets down; fix them or re-record "
                     f"deliberately with --baseline-grow):")
                for v in violations:
                    _err(f"  {v}")
                return 1
        return 0
    if args.format == "json":
        _out(json.dumps(manifest, indent=2, sort_keys=True))
    else:
        _out(na.format_text(manifest))
    if not os.path.exists(baseline_path):
        _err(f"ptpu audit-numerics: no baseline at {baseline_path} — "
             f"record one with --write-baseline (gate skipped).")
        return 0
    try:
        baseline = na.load_manifest(baseline_path)
    except (OSError, ValueError) as e:
        _err(f"ptpu audit-numerics: cannot read baseline: {e}")
        return 2
    if args.entry:
        # a subset run gates only the audited entries — the others
        # were not traced, not "no longer reproduced"
        keep = set(args.entry)
        baseline = {**baseline,
                    "entries": {k: v
                                for k, v in baseline["entries"].items()
                                if k in keep}}
    violations, shrinkable = na.diff_manifests(manifest, baseline)
    if shrinkable:
        _err(f"ptpu audit-numerics: {len(shrinkable)} baseline entr"
             f"{'y is' if len(shrinkable) == 1 else 'ies are'} no "
             f"longer fully reproduced — ratchet down with "
             f"--write-baseline:")
        for s in shrinkable:
            _err(f"  {s}")
    if violations:
        _err(f"ptpu audit-numerics: {len(violations)} precision "
             f"regression(s) vs {baseline_path}:")
        for v in violations:
            _err(f"  {v}")
        return 1
    _err("ptpu audit-numerics: traced dtype census matches the "
         "golden manifest.")
    return 0


def cmd_audit_lifecycle(args) -> int:
    """``ptpu audit-lifecycle`` — boot each subsystem (event / storage
    / engine servers, stream trainer, fleet aggregator, router
    autoscaler), drive start→serve→stop cycles, snapshot
    ``/proc/self`` threads/fds/sockets around them and gate the leak
    census against the committed golden manifest
    (``analysis/lifecycle_baseline.json``) with the same ratchet
    semantics as ``audit-hlo``/``audit-numerics``. The static
    lifecycle rules catch the leaks the AST can see; this catches the
    ones only a running process shows. Non-zero exit on any leak above
    the recorded allowance (see --baseline-grow);
    docs/static-analysis.md has the triage runbook."""
    from ..analysis import lifecycle_audit as la

    if args.list_entries:
        for name, (_b, desc) in la.ENTRY_POINTS.items():
            _out(f"{name}: {desc}")
        return 0
    try:
        manifest = la.run_audit(args.entry or None, cycles=args.cycles)
    except la.AuditError as e:
        _err(f"ptpu audit-lifecycle: {e}")
        return 2
    baseline_path = args.baseline or la.DEFAULT_BASELINE
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(manifest, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.write_baseline:
        cap = None
        if not args.baseline_grow and os.path.exists(baseline_path):
            try:
                cap = la.load_manifest(baseline_path)
            except (OSError, ValueError) as e:
                _err(f"ptpu audit-lifecycle: cannot read baseline: {e}")
                return 2
        la.write_manifest(baseline_path, manifest, cap=cap)
        _err(f"ptpu audit-lifecycle: wrote "
             f"{len(manifest['entries'])} entry point(s) to "
             f"{baseline_path}"
             f"{' (ratchet: shrink-only)' if cap is not None else ''}.")
        if cap is not None:
            violations, _ = la.diff_manifests(manifest, cap)
            if violations:
                _err(f"ptpu audit-lifecycle: {len(violations)} "
                     f"leak(s) were NOT absorbed (the baseline only "
                     f"ratchets down; fix them or re-record "
                     f"deliberately with --baseline-grow):")
                for v in violations:
                    _err(f"  {v}")
                return 1
        return 0
    if args.format == "json":
        _out(json.dumps(manifest, indent=2, sort_keys=True))
    else:
        _out(la.format_text(manifest))
    if not os.path.exists(baseline_path):
        _err(f"ptpu audit-lifecycle: no baseline at {baseline_path} — "
             f"record one with --write-baseline (gate skipped).")
        return 0
    try:
        baseline = la.load_manifest(baseline_path)
    except (OSError, ValueError) as e:
        _err(f"ptpu audit-lifecycle: cannot read baseline: {e}")
        return 2
    if args.entry:
        # a subset run gates only the audited entries — the others
        # were not cycled, not "no longer reproduced"
        keep = set(args.entry)
        baseline = {**baseline,
                    "entries": {k: v
                                for k, v in baseline["entries"].items()
                                if k in keep}}
    violations, shrinkable = la.diff_manifests(manifest, baseline)
    if shrinkable:
        _err(f"ptpu audit-lifecycle: {len(shrinkable)} baseline entr"
             f"{'y is' if len(shrinkable) == 1 else 'ies are'} no "
             f"longer fully reproduced — ratchet down with "
             f"--write-baseline:")
        for s in shrinkable:
            _err(f"  {s}")
    if violations:
        _err(f"ptpu audit-lifecycle: {len(violations)} resource "
             f"leak(s) vs {baseline_path}:")
        for v in violations:
            _err(f"  {v}")
        return 1
    _err("ptpu audit-lifecycle: every start->stop cycle released its "
         "threads, fds and sockets.")
    return 0


def cmd_template(args, storage: Storage) -> int:
    _out("Bundled engine templates (predictionio_tpu.templates):")
    _out("  recommendation  — ALS top-N (module: "
         "predictionio_tpu.templates.recommendation:recommendation_engine)")
    _out("  classification  — naive Bayes / random forest (…"
         "classification:classification_engine)")
    _out("  similarproduct  — ALS cosine / cooccurrence / like (…"
         "similarproduct:similarproduct_engine)")
    _out("  ecommerce       — ALS + popularity + filters (…"
         "ecommerce:ecommerce_engine)")
    return 0


def _find_channel(storage: Storage, app: App, name: str):
    """Resolve a channel by name within an app; None when absent."""
    return next((c for c in storage.channels().get_by_app_id(app.id)
                 if c.name == name), None)


def _confirm(prompt: str) -> bool:
    try:
        return input(f"{prompt} (y/N) ").strip().lower() == "y"
    except EOFError:
        return False


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ptpu",
        description="PredictionIO-TPU console (the reference's `pio`)")
    p.add_argument("--version", action="version",
                   version=f"%(prog)s {__version__}")
    sub = p.add_subparsers(dest="command", required=True)

    def add_engine_flags(sp):
        sp.add_argument("--engine-json", default="engine.json")
        sp.add_argument("--engine-id", default="")
        sp.add_argument("--engine-version", default="")

    sp = sub.add_parser("app", help="manage apps")
    app_sub = sp.add_subparsers(dest="app_command", required=True)
    s = app_sub.add_parser("new")
    s.add_argument("name")
    s.add_argument("--id", type=int, default=0)
    s.add_argument("--description")
    s.add_argument("--access-key", default="")
    app_sub.add_parser("list")
    s = app_sub.add_parser("show")
    s.add_argument("name")
    s = app_sub.add_parser("delete")
    s.add_argument("name")
    s.add_argument("-f", "--force", action="store_true")
    s = app_sub.add_parser("data-delete")
    s.add_argument("name")
    s.add_argument("--channel", default="")
    s.add_argument("-f", "--force", action="store_true")
    s = app_sub.add_parser("channel-new")
    s.add_argument("name")
    s.add_argument("channel")
    s = app_sub.add_parser("channel-delete")
    s.add_argument("name")
    s.add_argument("channel")
    s.add_argument("-f", "--force", action="store_true")

    sp = sub.add_parser("accesskey", help="manage access keys")
    ak_sub = sp.add_subparsers(dest="ak_command", required=True)
    s = ak_sub.add_parser("new")
    s.add_argument("app")
    s.add_argument("events", nargs="*")
    s.add_argument("--key", default="")
    s = ak_sub.add_parser("list")
    s.add_argument("--app", default="")
    s = ak_sub.add_parser("delete")
    s.add_argument("key")

    s = sub.add_parser("build", help="verify the engine variant loads")
    add_engine_flags(s)
    # AOT compile artifacts (ISSUE 19, docs/cold-start.md): serialize
    # the serving executables at build time so deploy warms by loading
    # them. The serving-envelope flags below are key-bearing and must
    # match the eventual `ptpu deploy` invocation.
    s.add_argument("--aot", action="store_true",
                   help="ahead-of-time compile the serving entry "
                        "points for the latest COMPLETED instance and "
                        "serialize them into --artifact-dir; a deploy "
                        "passing the same dir + serving flags warms "
                        "from the artifacts in milliseconds")
    s.add_argument("--artifact-dir", default="",
                   help="AOT artifact store root (default "
                        "$PTPU_ARTIFACT_DIR or ~/.ptpu/artifacts)")
    s.add_argument("--batching", action="store_true",
                   help="capture for a --batching deploy (pow2 batch "
                        "ladder up to --max-batch)")
    s.add_argument("--max-batch", type=int, default=128,
                   help="max queries per coalesced dispatch")
    s.add_argument("--serving-mode", default="single",
                   choices=["auto", "single", "replicated", "sharded"],
                   help="serving placement the deploy will use")
    s.add_argument("--serving-quant", default="off",
                   choices=["off", "bf16", "int8"],
                   help="serving-table quantization the deploy will "
                        "use")

    s = sub.add_parser("train", help="train an engine")
    add_engine_flags(s)
    s.add_argument("--skip-sanity-check", action="store_true")
    s.add_argument("--stop-after-read", action="store_true")
    s.add_argument("--stop-after-prepare", action="store_true")

    s = sub.add_parser("eval", help="run an evaluation")
    s.add_argument("evaluation",
                   help="module.path:evaluation_object")
    s.add_argument("engine_params_generator", nargs="?", default="",
                   help="module.path:params_generator (optional)")
    s.add_argument("--parallelism", type=int, default=1,
                   help="grid-walk thread pool size (packing and fold "
                        "prefixes are shared; >1 overlaps host work "
                        "with device dispatches)")

    s = sub.add_parser("deploy", help="deploy the latest trained engine")
    add_engine_flags(s)
    s.add_argument("--ip", default="0.0.0.0")
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--feedback", action="store_true")
    s.add_argument("--feedback-app-name", default="")
    s.add_argument("--accesskey", default="")
    s.add_argument("--cert", default="", help="PEM cert to serve HTTPS")
    s.add_argument("--key", default="", help="PEM private key")
    # literals, NOT `ServerConfig.<field>`: importing the server stack
    # here would pull jax into every storage-only CLI command. The
    # values are asserted equal to ServerConfig's defaults by
    # tests/test_cli.py::test_deploy_batching_defaults_match_config.
    s.add_argument("--batching", action="store_true",
                   help="coalesce concurrent queries into batched "
                        "device dispatches (the serving micro-batcher)")
    s.add_argument("--max-batch", type=int, default=128,
                   help="max queries per coalesced dispatch")
    s.add_argument("--batch-window-ms", type=float, default=2.0,
                   help="wait for a lone query before serving it solo")
    s.add_argument("--batch-pipeline", type=int, default=4,
                   help="staged pipeline: dispatch threads of a "
                        "single-binding deploy (a lane binding runs "
                        "one per lane)")
    s.add_argument("--queue-deadline-ms", type=float, default=30000.0,
                   help="per-query deadline covering queue wait "
                        "through readback; exceeded queries shed with "
                        "503 (pio_query_deadline_exceeded_total). "
                        "0 disables")
    s.add_argument("--assemble-workers", type=int, default=1,
                   help="staged pipeline: host threads parsing/"
                        "supplementing the next batch (raise for "
                        "storage-heavy supplements)")
    s.add_argument("--readback-workers", type=int, default=4,
                   help="staged pipeline: host threads blocking on "
                        "device results + serializing")
    s.add_argument("--pipeline-depth", type=int, default=0,
                   help="staged pipeline: bounded in-flight batches "
                        "per lane (the backpressure knob); 0 = auto "
                        "(2 on every backend)")
    s.add_argument("--cache", action="store_true",
                   help="serving cache hierarchy: query-result + "
                        "feature caches and the device-resident "
                        "hot-entity tier (docs/serving-cache.md)")
    s.add_argument("--cache-entries", type=int, default=8192,
                   help="query-result cache capacity (entries)")
    s.add_argument("--cache-ttl", type=float, default=30.0,
                   help="query-result staleness bound (seconds)")
    s.add_argument("--feature-ttl", type=float, default=5.0,
                   help="serving-time event-store read staleness "
                        "bound (seconds)")
    s.add_argument("--hot-entities", type=int, default=512,
                   help="hottest entities pinned on device (0 off)")
    s.add_argument("--debug-locks", action="store_true",
                   help="instrument every serving-stack lock: live "
                        "lock-order/re-entry detection, pio_lock_* "
                        "series, deadlock watchdog (staging tool; "
                        "PTPU_DEBUG_LOCKS=1 works too)")
    s.add_argument("--serving-mode", default="single",
                   choices=["auto", "single", "replicated", "sharded"],
                   help="mesh-wide serving (docs/sharded-serving.md): "
                        "replicated = full model copy per device, "
                        "micro-batches fan out per-device (~Nx qps); "
                        "sharded = factor tables row-sharded over the "
                        "(batch, model) mesh (models > one HBM); "
                        "auto = sharded when the model exceeds the "
                        "per-device HBM headroom, else replicated")
    s.add_argument("--serving-quant", default="off",
                   choices=["off", "bf16", "int8"],
                   help="row-quantized serving factor tables "
                        "(docs/kernels.md): int8 = per-row-scaled "
                        "int8 storage (~4x users per HBM, ~4x less "
                        "bandwidth per scored batch) with f32 "
                        "accumulation; bf16 halves both; guarded by "
                        "a deploy-time NDCG@10 parity probe that "
                        "auto-falls-back to f32")
    s.add_argument("--stream", action="store_true",
                   help="streaming incremental training "
                        "(docs/streaming.md): a trainer daemon tails "
                        "the event log and folds fresh events into "
                        "the serving model within seconds")
    s.add_argument("--stream-app", default="",
                   help="app whose event log the trainer tails "
                        "(defaults to --feedback-app-name)")
    s.add_argument("--stream-interval-ms", type=float, default=500.0,
                   help="fold-in poll fallback; in-process ingest "
                        "wakes the trainer immediately via the bus")
    s.add_argument("--stream-max-events", type=int, default=2048,
                   help="events consumed per fold-in micro-batch")
    s.add_argument("--stream-consumer", default="stream-trainer",
                   help="durable cursor identity (resume point "
                        "survives restarts under this name)")
    s.add_argument("--stream-drift-threshold", type=float, default=1.0,
                   help="DriftMonitor score that flags a full retrain")
    s.add_argument("--stream-canary-probes", type=int, default=8,
                   help="touched-entity probes gating each fold-in "
                        "delta (0 disables the canary gate)")
    s.add_argument("--faults", default="",
                   help="fault-injection spec for failure drills "
                        "(docs/reliability.md), e.g. "
                        "'serving.lane=error,lane=1,times=5'; the "
                        "PTPU_FAULTS env var works on every server")
    s.add_argument("--no-trace", action="store_true",
                   help="disable end-to-end request tracing "
                        "(docs/tracing.md; on by default — every "
                        "request traced, only slow/error/503/fault "
                        "traces retained)")
    s.add_argument("--trace-ring", type=int, default=512,
                   help="retained traces the flight-recorder ring "
                        "holds (oldest evicted)")
    s.add_argument("--trace-slow-ms", type=float, default=0.0,
                   help="fixed slow-retention threshold in ms; 0 = "
                        "adaptive (live p99 of traced durations)")
    s.add_argument("--access-log-sample", type=float, default=1.0,
                   help="fraction of successful requests written to "
                        "the JSON access log (errors/503s always "
                        "log); 1.0 = every request")
    s.add_argument("--profile-dir", default="",
                   help="artifact dir for POST /profile device "
                        "captures (default $PTPU_PROFILE_DIR or "
                        "<tmp>/ptpu-profiles)")
    s.add_argument("--slo-specs", default="",
                   help="SLO spec file (docs/slo.md) evaluated "
                        "continuously against this server's metrics; "
                        "default: the built-in availability/latency/"
                        "freshness objectives")
    s.add_argument("--slo-interval-ms", type=float, default=1000.0,
                   help="SLO evaluation tick; 0 disables the engine")
    s.add_argument("--hot-keys-k", type=int, default=128,
                   help="Space-Saving hot-key sketch capacity: every "
                        "entity hotter than 1/k of query traffic is "
                        "guaranteed tracked (pio_hot_keys, the "
                        "/status.json hotKeys block; docs/fleet.md). "
                        "0 disables")
    s.add_argument("--fleet-of", type=int, default=1,
                   help="deploy N replicas on consecutive ports "
                        "fronted by the fleet aggregator "
                        "(docs/fleet.md): merged metrics, fleet-scoped "
                        "SLOs, cross-replica trace lookup")
    s.add_argument("--fleet-port", type=int, default=8200,
                   help="port the fleet aggregator listens on "
                        "(--fleet-of > 1)")
    s.add_argument("--fleet-scrape-interval-ms", type=float,
                   default=5000.0,
                   help="aggregator scrape cadence over the replicas")
    s.add_argument("--router-port", type=int, default=8100,
                   help="port the entity-affinity query router "
                        "listens on (--fleet-of > 1; "
                        "docs/autoscaling.md). Clients send "
                        "/queries.json here instead of to a replica")
    s.add_argument("--autoscale", action="store_true",
                   help="run the SLO-driven autoscaler: scale out on "
                        "fast-window burn or low capacity headroom, "
                        "in against the CAPACITY.json knee with "
                        "hysteresis + cooldown (docs/autoscaling.md)")
    s.add_argument("--min-replicas", type=int, default=1,
                   help="autoscaler floor (--autoscale)")
    s.add_argument("--max-replicas", type=int, default=8,
                   help="autoscaler ceiling (--autoscale)")
    s.add_argument("--capacity", default="",
                   help="CAPACITY.json for the fleet headroom gauge "
                        "and the autoscaler's knee model "
                        "(benchmarks/load_harness.py output)")
    s.add_argument("--artifact-dir", default="",
                   help="warm from the AOT artifact store `ptpu build "
                        "--aot` wrote there (docs/cold-start.md): "
                        "deploy loads serialized serving executables "
                        "instead of compiling, with automatic "
                        "fallback to compile on any key mismatch. "
                        "Empty disables (the compile warm)")

    s = sub.add_parser("undeploy", help="stop a deployed engine")
    s.add_argument("--ip", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--accesskey", default="",
                   help="access key if the server was deployed with one")
    s.add_argument("--https", action="store_true",
                   help="the server was deployed with --cert/--key")
    s.add_argument("--insecure", action="store_true",
                   help="skip TLS certificate verification (self-signed "
                        "local certs only)")

    s = sub.add_parser(
        "release",
        help="progressive delivery: list/show/pin releases, drive "
             "canary/shadow rollouts, promote, roll back")
    rel_sub = s.add_subparsers(dest="release_command", required=True)

    def add_release_flags(sp, server: bool = False):
        add_engine_flags(sp)
        sp.add_argument("--reason", default="",
                        help="recorded in the release history")
        if server:
            sp.add_argument("--ip", default="127.0.0.1")
            sp.add_argument("--port", type=int, default=8000)
            sp.add_argument("--accesskey", default="")
            sp.add_argument("--https", action="store_true")
            sp.add_argument("--insecure", action="store_true")

    rel_sub.add_parser("list", help="every engine with release state")
    r = rel_sub.add_parser("show", help="full state + history (JSON)")
    add_release_flags(r)
    r.add_argument("--limit", type=int, default=50,
                   help="history entries to include")
    r = rel_sub.add_parser(
        "pin", help="pin deploy/reload to an instance id")
    add_release_flags(r)
    r.add_argument("instance_id", nargs="?", default="")
    r.add_argument("--clear", action="store_true",
                   help="unpin (bind latest COMPLETED again)")
    r = rel_sub.add_parser(
        "canary", help="start a health-gated canary of an instance "
                       "on the running engine server")
    add_release_flags(r, server=True)
    r.add_argument("instance_id")
    r.add_argument("--fraction", default="",
                   help="initial candidate traffic fraction "
                        "(e.g. 0.05 or 5%%; default: first ramp step)")
    r.add_argument("--shadow", action="store_true",
                   help="mirror queries to the candidate without "
                        "returning its answers (never auto-promotes)")
    r = rel_sub.add_parser(
        "promote", help="promote the live candidate to pinned stable")
    add_release_flags(r, server=True)
    r = rel_sub.add_parser(
        "rollback", help="abort the live candidate (or revert stable "
                         "to the previous release)")
    add_release_flags(r, server=True)
    r = rel_sub.add_parser(
        "status", help="live /release.json from the engine server "
                       "(falls back to storage state)")
    add_release_flags(r, server=True)

    s = sub.add_parser(
        "cache", help="serving cache: per-tier stats, operator flush")
    cache_sub = s.add_subparsers(dest="cache_command", required=True)
    for name, helptext in (("stats", "per-tier hit/miss/eviction/"
                                     "invalidation stats"),
                           ("flush", "flush every cache tier")):
        c = cache_sub.add_parser(name, help=helptext)
        c.add_argument("--ip", default="127.0.0.1")
        c.add_argument("--port", type=int, default=8000)
        c.add_argument("--accesskey", default="")
        c.add_argument("--https", action="store_true")
        c.add_argument("--insecure", action="store_true")

    s = sub.add_parser(
        "stream", help="streaming incremental training: attach/stop/"
                       "inspect the event→model loop on a running "
                       "engine server (docs/streaming.md)")
    stream_sub = s.add_subparsers(dest="stream_command", required=True)
    for name, helptext in (
            ("start", "attach the incremental trainer"),
            ("status", "trainer state, cursor, drift, model lineage"),
            ("stop", "stop the trainer (the durable cursor stays)")):
        c = stream_sub.add_parser(name, help=helptext)
        c.add_argument("--ip", default="127.0.0.1")
        c.add_argument("--port", type=int, default=8000)
        c.add_argument("--accesskey", default="")
        c.add_argument("--https", action="store_true")
        c.add_argument("--insecure", action="store_true")
        if name == "start":
            c.add_argument("--app", default="",
                           help="app whose event log to tail (falls "
                                "back to the server's deploy config)")
            c.add_argument("--channel", default="")
            c.add_argument("--consumer", default="",
                           help="durable cursor identity")
            c.add_argument("--interval-ms", type=float, default=None)
            c.add_argument("--max-events", type=int, default=None)
            c.add_argument("--drift-threshold", type=float,
                           default=None)
            c.add_argument("--canary-probes", type=int, default=None)

    s = sub.add_parser(
        "slo", help="service-level objectives: live burn rates from a "
                    "running server, or capacity-gate a load_harness "
                    "run against committed SLOs (docs/slo.md)")
    slo_sub = s.add_subparsers(dest="slo_command", required=True)
    c = slo_sub.add_parser(
        "status", help="per-spec burn rates, budgets, breach state "
                       "from GET /slo.json (exit 1 while burning)")
    c.add_argument("--ip", default="127.0.0.1")
    c.add_argument("--port", type=int, default=8000)
    c.add_argument("--accesskey", default="")
    c.add_argument("--https", action="store_true")
    c.add_argument("--insecure", action="store_true")
    c = slo_sub.add_parser(
        "check", help="gate a CAPACITY.json against the committed "
                      "spec file's capacity section (the CI merge "
                      "gate; regressions fail naming spec, window, "
                      "and measured value)")
    c.add_argument("--capacity", default="CAPACITY.json",
                   help="capacity model emitted by "
                        "benchmarks/load_harness.py")
    c.add_argument("--specs", default="slo/specs/ci.json",
                   help="committed SLO spec file with the capacity "
                        "gates")
    c.add_argument("--update", action="store_true",
                   help="ratchet: tighten committed gates toward a "
                        "better measurement (never loosens)")

    s = sub.add_parser(
        "trace", help="flight recorder: list the slowest retained "
                      "traces or export one as Perfetto JSON "
                      "(docs/tracing.md)")
    s.add_argument("--ip", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--accesskey", default="")
    s.add_argument("--https", action="store_true")
    s.add_argument("--insecure", action="store_true")
    s.add_argument("--id", default="",
                   help="export this retained trace as Chrome/"
                        "Perfetto trace-event JSON")
    s.add_argument("--slowest", type=int, default=None,
                   help="list the N slowest retained traces")
    s.add_argument("-o", "--output", default="",
                   help="output file for --id (default "
                        "trace-<id>.json)")

    s = sub.add_parser(
        "fleet", help="fleet observability plane (docs/fleet.md): run "
                      "the aggregator that merges N replicas' metrics "
                      "exactly, or query a running one")
    fleet_sub = s.add_subparsers(dest="fleet_command", required=True)
    c = fleet_sub.add_parser(
        "serve", help="run the aggregator over --replicas: merged "
                      "/metrics, fleet SLOs, cross-replica traces, "
                      "hot keys")
    c.add_argument("--replicas", required=True,
                   help="comma-separated replica addresses "
                        "(host:port or full URLs)")
    c.add_argument("--ip", default="0.0.0.0")
    c.add_argument("--port", type=int, default=8200)
    c.add_argument("--scrape-interval-ms", type=float, default=5000.0,
                   help="how often each replica's /metrics.json and "
                        "/status.json are pulled and merged")
    c.add_argument("--stale-after-ms", type=float, default=0.0,
                   help="a replica unscraped this long is DOWN "
                        "(default: 3x the scrape interval)")
    c.add_argument("--slo-specs", default="",
                   help="SLO spec file evaluated against the MERGED "
                        "series (fleet-scoped burn rates); default: "
                        "built-in availability/latency objectives")
    c.add_argument("--slo-interval-ms", type=float, default=1000.0,
                   help="fleet SLO evaluation tick; 0 disables")
    c.add_argument("--capacity", default="",
                   help="CAPACITY.json (load_harness output); its "
                        "knee qps feeds pio_fleet_capacity_headroom")
    c.add_argument("--hot-keys-k", type=int, default=128,
                   help="fleet-wide merged hot-key sketch capacity")
    c.add_argument("--timeout-sec", type=float, default=5.0,
                   help="per-replica scrape/fan-out timeout")
    c.add_argument("--accesskey", default="",
                   help="require ?accessKey= on POST /scrape and "
                        "POST /stop")
    c.add_argument("--cert", default="", help="PEM cert to serve HTTPS")
    c.add_argument("--key", default="", help="PEM private key")
    for name, helptext in (
            ("status", "per-replica liveness/lag/flags + fleet "
                       "headroom (exit 1 on down replicas or a "
                       "burning fleet SLO; a replica the autoscaler "
                       "removed on purpose is NOT down)"),
            ("slo", "fleet SLO burn rates from the merged series"),
            ("trace", "cross-replica flight-recorder lookup"),
            ("hotkeys", "fleet-wide hot-key top-K"),
            ("route", "query-router view: ring membership, per-"
                      "backend state/inflight, hot-key spill "
                      "(--key shows one entity's placement)"),
            ("scale", "ask the autoscaler for a replica count "
                      "(clamped to --min/--max-replicas)")):
        c = fleet_sub.add_parser(name, help=helptext)
        c.add_argument("--ip", default="127.0.0.1")
        c.add_argument("--port", type=int, default=8200)
        c.add_argument("--accesskey", default="")
        c.add_argument("--https", action="store_true")
        c.add_argument("--insecure", action="store_true")
        if name == "trace":
            c.add_argument("--id", default="",
                           help="fan the id out to every replica and "
                                "export the hit as Perfetto JSON")
            c.add_argument("--slowest", type=int, default=None,
                           help="the fleet's N slowest retained "
                                "traces, merged")
            c.add_argument("-o", "--output", default="",
                           help="output file for --id")
        if name == "hotkeys":
            c.add_argument("--top", type=int, default=16,
                           help="keys to list")
        if name == "route":
            c.add_argument("--key", default="",
                           help="show where this entity id routes "
                                "(affinity + preference list)")
        if name == "scale":
            c.add_argument("--to", type=int, required=True,
                           help="desired replica count")
            c.add_argument("--reason", default="",
                           help="recorded in the decision log")

    s = sub.add_parser("batchpredict", help="bulk predict JSON lines")
    add_engine_flags(s)
    s.add_argument("--input", required=True)
    s.add_argument("--output", required=True)

    s = sub.add_parser("eventserver", help="start the Event Server")
    s.add_argument("--ip", default="0.0.0.0")
    s.add_argument("--port", type=int, default=7070)
    s.add_argument("--stats", action="store_true")
    s.add_argument("--cert", default="", help="PEM cert to serve HTTPS")
    s.add_argument("--key", default="", help="PEM private key")

    s = sub.add_parser("storageserver",
                       help="serve storage to REMOTE-backend clients")
    s.add_argument("--ip", default="0.0.0.0")
    s.add_argument("--port", type=int, default=7077)
    s.add_argument("--secret", default="",
                   help="shared secret clients must send")
    s.add_argument("--cert", default="", help="PEM cert to serve HTTPS")
    s.add_argument("--key", default="", help="PEM private key")

    s = sub.add_parser("adminserver", help="start the admin API")
    s.add_argument("--ip", default="127.0.0.1")
    s.add_argument("--port", type=int, default=7071)
    s.add_argument("--accesskey", default="")
    s.add_argument("--cert", default="")
    s.add_argument("--key", default="")

    s = sub.add_parser("dashboard", help="start the evaluation dashboard")
    s.add_argument("--ip", default="127.0.0.1")
    s.add_argument("--port", type=int, default=9000)
    s.add_argument("--accesskey", default="")
    s.add_argument("--cert", default="")
    s.add_argument("--key", default="")

    s = sub.add_parser("start-all", help="start event/admin/dashboard "
                       "(and optionally storage) servers as daemons "
                       "with pidfiles")
    s.add_argument("--ip", default="0.0.0.0")
    s.add_argument("--pid-dir", default="",
                   help="pidfile/log dir (default ~/.ptpu or "
                        "$PIO_PID_DIR)")
    s.add_argument("--eventserver-port", dest="event_port", type=int,
                   default=0)
    s.add_argument("--adminserver-port", dest="admin_port", type=int,
                   default=0)
    s.add_argument("--dashboard-port", dest="dash_port", type=int,
                   default=0)
    s.add_argument("--with-storageserver", action="store_true",
                   help="also start the remote-backend storage server")
    s.add_argument("--storageserver-port", dest="storage_port",
                   type=int, default=0)
    s.add_argument("--storage-secret", default="")
    s.add_argument("--start-timeout", type=float, default=30.0)

    s = sub.add_parser("stop-all", help="stop every start-all daemon")
    s.add_argument("--pid-dir", default="")
    s.add_argument("--stop-timeout", type=float, default=10.0)

    s = sub.add_parser("status", help="check environment and storage")
    s.add_argument("--ip", default="",
                   help="also query a live engine server's "
                        "/status.json for serving model lineage")
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--accesskey", default="")
    s.add_argument("--https", action="store_true")
    s.add_argument("--insecure", action="store_true")

    s = sub.add_parser("export", help="export events to a JSON-lines file")
    s.add_argument("--appid", type=int, default=0)
    s.add_argument("--app", default="")
    s.add_argument("--channel", default="")
    s.add_argument("--output", required=True)

    s = sub.add_parser("import", help="import events from JSON lines")
    s.add_argument("--appid", type=int, default=0)
    s.add_argument("--app", default="")
    s.add_argument("--channel", default="")
    s.add_argument("--input", required=True)

    s = sub.add_parser("check", help="JAX-aware + concurrency + Pallas"
                       "-kernel static analysis, interprocedural "
                       "(host-sync, recompile, donation, sharding, "
                       "config, lock-discipline, VMEM-budget, DMA, "
                       "accumulator-precision lints)")
    s.add_argument("paths", nargs="*",
                   help="files/dirs to check (default: predictionio_tpu)")
    s.add_argument("--rule", action="append", default=[],
                   help="run only the named rule (repeatable)")
    s.add_argument("--list-rules", action="store_true",
                   help="print the rule catalogue and exit")
    s.add_argument("--format", choices=("text", "json", "sarif"),
                   default="text",
                   help="output format (sarif for GitHub code-scanning "
                        "PR annotations)")
    s.add_argument("--baseline", default="",
                   help="baseline file: exit 1 only on findings NOT "
                        "recorded in it (legacy-debt burn-down)")
    s.add_argument("--write-baseline", action="store_true",
                   help="record current findings into --baseline FILE; "
                        "against an existing baseline this only "
                        "RATCHETS (removes/decrements entries) and "
                        "fails on findings beyond the recorded debt")
    s.add_argument("--baseline-grow", action="store_true",
                   help="with --write-baseline: allow recording NEW "
                        "debt (e.g. when enabling a rule) instead of "
                        "the default shrink-only ratchet")

    s = sub.add_parser("audit-hlo", help="compile the SPMD entry "
                       "points on a forced 8-device CPU mesh and diff "
                       "the HLO collectives against the committed "
                       "golden manifest (the runtime complement of "
                       "the ptpu check sharding rules)")
    s.add_argument("--entry", action="append", default=[],
                   help="audit only the named entry point (repeatable)")
    s.add_argument("--list-entries", action="store_true",
                   help="print the entry-point catalogue and exit")
    s.add_argument("--format", choices=("text", "json"), default="text",
                   help="output format for the fresh manifest")
    s.add_argument("--out", default="",
                   help="also write the fresh manifest JSON to FILE "
                        "(the CI artifact)")
    s.add_argument("--baseline", default="",
                   help="golden manifest to gate against (default: "
                        "the committed analysis/hlo_baseline.json)")
    s.add_argument("--write-baseline", action="store_true",
                   help="record the fresh manifest as the baseline; "
                        "against an existing one this only RATCHETS "
                        "(shrinks counts/temps) and fails on growth")
    s.add_argument("--baseline-grow", action="store_true",
                   help="with --write-baseline: allow recording new "
                        "collectives/entries (deliberate schedule "
                        "changes) instead of the shrink-only ratchet")

    s = sub.add_parser("audit-numerics", help="abstract-interpret the "
                       "registered numeric entry points and diff the "
                       "dtype census (casts, accumulation dtypes, "
                       "bytes) against the committed golden manifest "
                       "(the runtime complement of the ptpu check "
                       "dtype-flow rules)")
    s.add_argument("--entry", action="append", default=[],
                   help="audit only the named entry point (repeatable)")
    s.add_argument("--list-entries", action="store_true",
                   help="print the entry-point catalogue and exit")
    s.add_argument("--format", choices=("text", "json"), default="text",
                   help="output format for the fresh manifest")
    s.add_argument("--out", default="",
                   help="also write the fresh manifest JSON to FILE "
                        "(the CI artifact)")
    s.add_argument("--baseline", default="",
                   help="golden manifest to gate against (default: the "
                        "committed analysis/numerics_baseline.json)")
    s.add_argument("--write-baseline", action="store_true",
                   help="record the fresh manifest as the baseline; "
                        "against an existing one this only RATCHETS "
                        "(shrinks counts/bytes) and fails on growth")
    s.add_argument("--baseline-grow", action="store_true",
                   help="with --write-baseline: allow recording new "
                        "casts/entries (deliberate precision changes) "
                        "instead of the shrink-only ratchet")

    s = sub.add_parser("audit-lifecycle", help="boot each subsystem, "
                       "drive start->serve->stop cycles, snapshot "
                       "/proc threads/fds/sockets around them and "
                       "gate the leak census against the committed "
                       "golden manifest (the runtime complement of "
                       "the ptpu check lifecycle rules)")
    s.add_argument("--entry", action="append", default=[],
                   help="audit only the named entry point (repeatable)")
    s.add_argument("--list-entries", action="store_true",
                   help="print the entry-point catalogue and exit")
    s.add_argument("--cycles", type=int, default=3,
                   help="measured start->stop cycles per entry "
                        "(default 3; one extra warmup cycle always "
                        "runs unmeasured)")
    s.add_argument("--format", choices=("text", "json"), default="text",
                   help="output format for the fresh manifest")
    s.add_argument("--out", default="",
                   help="also write the fresh manifest JSON to FILE "
                        "(the CI artifact)")
    s.add_argument("--baseline", default="",
                   help="golden manifest to gate against (default: the "
                        "committed analysis/lifecycle_baseline.json)")
    s.add_argument("--write-baseline", action="store_true",
                   help="record the fresh manifest as the baseline; "
                        "against an existing one this only RATCHETS "
                        "(shrinks the allowed leaks) and fails on "
                        "growth")
    s.add_argument("--baseline-grow", action="store_true",
                   help="with --write-baseline: allow recording new "
                        "entries / larger allowances (deliberate "
                        "daemon changes) instead of the shrink-only "
                        "ratchet")

    sub.add_parser("template", help="list bundled engine templates")
    sub.add_parser("shell", help="interactive shell with storage preloaded")
    s = sub.add_parser("run", help="run module.path:callable with storage "
                                   "configured")
    s.add_argument("target")
    s.add_argument("args", nargs="*")
    sub.add_parser("version", help="print version")
    return p


COMMANDS = {
    "app": cmd_app,
    "accesskey": cmd_accesskey,
    "build": cmd_build,
    "train": cmd_train,
    "eval": cmd_eval,
    "deploy": cmd_deploy,
    "undeploy": cmd_undeploy,
    "release": cmd_release,
    "cache": cmd_cache,
    "stream": cmd_stream,
    "slo": cmd_slo,
    "trace": cmd_trace,
    "batchpredict": cmd_batchpredict,
    "start-all": cmd_start_all,
    "stop-all": cmd_stop_all,
    "eventserver": cmd_eventserver,
    "storageserver": cmd_storageserver,
    "adminserver": cmd_adminserver,
    "dashboard": cmd_dashboard,
    "status": cmd_status,
    "shell": cmd_shell,
    "run": cmd_run,
    "export": cmd_export,
    "import": cmd_import,
    "template": cmd_template,
}


def main(argv: Optional[List[str]] = None,
         storage: Optional[Storage] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.command == "version":
        _out(__version__)
        return 0
    if args.command == "check":
        # pure-AST lint: needs neither storage nor jax
        return cmd_check(args)
    if args.command == "fleet":
        # pure HTTP against replicas/aggregator: no storage, no jax
        return cmd_fleet(args)
    if args.command == "audit-hlo":
        # needs jax on a forced virtual mesh, but no storage; the
        # device topology MUST be pinned before the first jax import
        from ..analysis.hlo_audit import ensure_cpu_devices

        ensure_cpu_devices()
        return cmd_audit_hlo(args)
    if args.command == "audit-numerics":
        # jaxpr tracing only (no compile), but half the entries trace
        # through 8-device meshes — same topology pin as audit-hlo
        from ..analysis.numerics_audit import ensure_cpu_devices

        ensure_cpu_devices()
        return cmd_audit_numerics(args)
    if args.command == "audit-lifecycle":
        # boots real (loopback) servers; the engine entries train and
        # serve a tiny model — pin host devices before the first jax
        # import so the audit never waits on an accelerator runtime
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return cmd_audit_lifecycle(args)
    if args.command in ("train", "eval", "deploy", "batchpredict",
                        "run", "shell", "status"):
        # device-using commands share one persistent XLA program cache
        # (the JVM-warmup analogue); storage-only commands skip it so
        # they never pay the jax import
        from ..utils.platform import enable_compilation_cache
        enable_compilation_cache()
    if os.environ.get("PIO_COORDINATOR") \
            or os.environ.get("PIO_NUM_PROCESSES"):
        # join the multi-controller system before any device use (the
        # spark-submit --master role; TPU pods auto-detect without these)
        from ..parallel.multihost import initialize_distributed

        initialize_distributed()
    st = storage if storage is not None else get_storage()
    return COMMANDS[args.command](args, st)


if __name__ == "__main__":
    sys.exit(main())
