"""Unified telemetry tests (ISSUE 2): histogram math, Prometheus
exposition validity, per-phase spans through a real in-process engine
server (batched and unbatched), transfer-guard counter wiring, and
memory-boundedness of the span registry under 100k records."""

import contextlib
import json
import logging
import re
import sys
import threading
import urllib.error
import urllib.request
from datetime import datetime, timezone

import numpy as np
import pytest

from predictionio_tpu.obs import (
    DEFAULT_LATENCY_BOUNDS,
    MetricsRegistry,
    StreamingHistogram,
    TransferGuardCounter,
    exponential_bounds,
    linear_bounds,
)


# ---------------------------------------------------------------------------
# histogram bucket / percentile math
# ---------------------------------------------------------------------------

class TestStreamingHistogram:
    def test_bounds_validation(self):
        with pytest.raises(ValueError):
            StreamingHistogram([])
        with pytest.raises(ValueError):
            StreamingHistogram([1.0, 1.0])
        with pytest.raises(ValueError):
            StreamingHistogram([2.0, 1.0])
        with pytest.raises(ValueError):
            exponential_bounds(0, 2, 3)
        with pytest.raises(ValueError):
            linear_bounds(0, -1, 3)

    def test_bucket_assignment_le_semantics(self):
        h = StreamingHistogram([1.0, 2.0, 4.0])
        for v in (0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 100.0):
            h.record(v)
        # cumulative: le=1 → {0.5, 1.0}; le=2 → +{1.5, 2.0};
        # le=4 → +{3.0, 4.0}; +Inf → +{100.0}
        assert h.bucket_counts() == [
            (1.0, 2), (2.0, 4), (4.0, 6), (float("inf"), 7)]
        assert h.count == 7
        assert h.max == 100.0
        assert h.min == 0.5
        assert h.sum == pytest.approx(112.0)

    def test_percentiles_uniform_distribution(self):
        # 1..1000 into fine linear buckets: interpolation error is
        # bounded by one bucket width (10)
        h = StreamingHistogram(linear_bounds(10.0, 10.0, 100))
        for v in range(1, 1001):
            h.record(float(v))
        assert h.quantile(0.5) == pytest.approx(500, abs=10)
        assert h.quantile(0.9) == pytest.approx(900, abs=10)
        assert h.quantile(0.99) == pytest.approx(990, abs=10)
        assert h.quantile(1.0) == pytest.approx(1000, abs=10)

    def test_percentiles_skewed_distribution(self):
        # 99 fast + 1 slow: p50 stays in the fast bucket, p99+ sees the
        # tail — the exact signal raw-mean bookkeeping hides
        h = StreamingHistogram(exponential_bounds(0.001, 2.0, 20))
        for _ in range(99):
            h.record(0.002)
        h.record(10.0)
        assert h.quantile(0.5) < 0.01
        # p99 of 99 fast + 1 slow is still fast — the tail shows at
        # p99.9 and max (exactly why max is part of the snapshot)
        assert h.quantile(0.999) > 1.0
        s = h.snapshot()
        assert s["count"] == 100
        assert s["p99"] >= s["p50"]
        assert s["max"] == 10.0

    def test_quantile_clamped_to_observed_range(self):
        h = StreamingHistogram([1.0, 100.0])
        h.record(5.0)
        h.record(6.0)
        for q in (0.0, 0.5, 1.0):
            assert 5.0 <= h.quantile(q) <= 6.0

    def test_empty_histogram(self):
        h = StreamingHistogram()
        assert h.quantile(0.5) is None
        assert h.snapshot() == {"count": 0}
        assert h.count == 0 and h.max == 0.0

    def test_o1_memory_under_100k_records(self):
        h = StreamingHistogram(DEFAULT_LATENCY_BOUNDS)
        baseline_cells = len(h._counts)
        baseline_size = sys.getsizeof(h._counts)
        rng = np.random.default_rng(0)
        for v in rng.lognormal(-5, 2, size=100_000):
            h.record(float(v))
        assert h.count == 100_000
        # the whole state is still the same fixed bucket array
        assert len(h._counts) == baseline_cells
        assert sys.getsizeof(h._counts) == baseline_size
        assert h.quantile(0.99) is not None

    def test_thread_safety_no_lost_updates(self):
        h = StreamingHistogram([1.0])
        n, threads = 10_000, 8

        def hammer():
            for _ in range(n):
                h.record(0.5)

        ts = [threading.Thread(target=hammer) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.count == n * threads
        assert h.bucket_counts()[0][1] == n * threads


# ---------------------------------------------------------------------------
# Prometheus exposition format
# ---------------------------------------------------------------------------

_METRIC_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' (-?[0-9.eE+-]+|[+-]Inf|NaN)$')


def validate_exposition(text: str):
    """Grammar + histogram-consistency validation; returns the parsed
    (name → type) map."""
    assert text.endswith("\n")
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram")
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        assert _METRIC_LINE.match(line), f"bad line: {line!r}"
    return types


class TestPrometheusExposition:
    def test_render_all_kinds(self):
        reg = MetricsRegistry()
        reg.counter("t_requests_total", "requests").labels(
            method="GET", status="200").inc(3)
        reg.gauge("t_temperature", "a gauge").set(36.6)
        h = reg.histogram("t_latency_seconds", "latency",
                          bounds=[0.1, 1.0])
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.render()
        types = validate_exposition(text)
        assert types["t_requests_total"] == "counter"
        assert types["t_temperature"] == "gauge"
        assert types["t_latency_seconds"] == "histogram"
        assert 't_requests_total{method="GET",status="200"} 3' in text
        assert 't_latency_seconds_bucket{le="0.1"} 1' in text
        assert 't_latency_seconds_bucket{le="1"} 2' in text
        assert 't_latency_seconds_bucket{le="+Inf"} 3' in text
        assert "t_latency_seconds_count 3" in text
        assert "t_latency_seconds_sum 5.55" in text

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("t_esc_total", "escaping").labels(
            path='we"ird\\path\nline').inc()
        text = reg.render()
        validate_exposition(text)
        assert r'path="we\"ird\\path\nline"' in text

    def test_help_escaping_and_type_lines(self):
        reg = MetricsRegistry()
        reg.gauge("t_g", "multi\nline \\ help").set(1)
        text = reg.render()
        assert "# HELP t_g multi\\nline \\\\ help" in text
        assert "# TYPE t_g gauge" in text

    def test_invalid_names_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("bad-name", "nope")
        with pytest.raises(ValueError):
            reg.counter("t_ok_total", "ok").labels(**{"0bad": "v"})

    def test_kind_conflict_rejected(self):
        reg = MetricsRegistry()
        reg.counter("t_same", "x")
        with pytest.raises(ValueError):
            reg.gauge("t_same", "x")

    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("t_c_total", "x")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_fn_failure_reads_zero(self):
        reg = MetricsRegistry()
        reg.gauge("t_broken", "x", fn=lambda: 1 / 0)
        assert "t_broken 0" in reg.render()

    def test_snapshot_shapes(self):
        reg = MetricsRegistry()
        reg.counter("t_plain_total", "x").inc(2)
        reg.histogram("t_h_seconds", "x",
                      bounds=[1.0]).labels(phase="a").observe(0.5)
        snap = reg.snapshot()
        assert snap["t_plain_total"] == 2
        assert snap["t_h_seconds"]["phase=a"]["count"] == 1
        assert "p99" in snap["t_h_seconds"]["phase=a"]

    def test_collector_errors_isolated(self):
        reg = MetricsRegistry()

        def boom():
            raise RuntimeError("collector down")

        reg.register_collector(boom)
        reg.gauge("t_alive", "x").set(1)
        assert "t_alive 1" in reg.render()


# ---------------------------------------------------------------------------
# transfer-guard counter wiring
# ---------------------------------------------------------------------------

class TestTransferGuardCounter:
    def test_counts_guard_log_records(self):
        TransferGuardCounter.install()
        before = TransferGuardCounter.total()
        logging.getLogger("jax").warning(
            "Disallowed host-to-device transfer: aval=ShapedArray(...)")
        assert TransferGuardCounter.total() == before + 1
        # unrelated records do not count
        logging.getLogger("jax").warning("compiling module jit_step")
        assert TransferGuardCounter.total() == before + 1

    def test_direct_count_and_registry_gauge(self):
        from predictionio_tpu.obs import register_runtime_metrics

        reg = MetricsRegistry()
        register_runtime_metrics(reg, server="test")
        before = TransferGuardCounter.total()
        TransferGuardCounter.count(2)
        assert TransferGuardCounter.total() == before + 2
        text = reg.render()
        m = re.search(
            r"^pio_transfer_guard_violations_total (\d+)$", text,
            re.MULTILINE)
        assert m and int(m.group(1)) == TransferGuardCounter.total()

    def test_install_idempotent(self):
        h1 = TransferGuardCounter.install()
        h2 = TransferGuardCounter.install()
        assert h1 is h2
        root_handlers = [h for h in logging.getLogger().handlers
                         if isinstance(h, TransferGuardCounter)]
        assert len(root_handlers) == 1


# ---------------------------------------------------------------------------
# per-phase spans through a REAL in-process engine server
# ---------------------------------------------------------------------------

def _deploy_synthetic(batching: bool):
    from predictionio_tpu.controller import Context
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.storage import App, Storage
    from predictionio_tpu.data.storage.base import (
        STATUS_COMPLETED,
        EngineInstance,
    )
    from predictionio_tpu.models.als import ALSModel, ALSParams
    from predictionio_tpu.server.engineserver import (
        QueryServer,
        ServerConfig,
        create_engine_server,
    )
    from predictionio_tpu.templates.recommendation import (
        default_engine_params,
        recommendation_engine,
    )

    rank, n_users, n_items = 4, 16, 32
    rng = np.random.default_rng(0)
    model = ALSModel(
        user_factors=rng.standard_normal((n_users, rank)).astype(
            np.float32),
        item_factors=rng.standard_normal((n_items, rank)).astype(
            np.float32),
        n_users=n_users, n_items=n_items,
        user_ids=BiMap({f"u{i}": i for i in range(n_users)}),
        item_ids=BiMap({f"i{i}": i for i in range(n_items)}),
        params=ALSParams(rank=rank))
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "obsapp"))
    ctx = Context(app_name="obsapp", _storage=storage)
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="obs", status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="obs", engine_version="1", engine_variant="e.json",
        engine_factory="synthetic")
    qs = QueryServer(ctx, recommendation_engine(),
                     default_engine_params("obsapp", rank=rank),
                     [model], inst,
                     ServerConfig(warm_start=False, batching=batching,
                                  max_batch=8, batch_window_ms=5.0))
    srv = create_engine_server(qs, host="127.0.0.1", port=0)
    srv.start_background()
    return qs, srv


def _call(port, method, path, body=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            raw = resp.read()
            ctype = resp.headers.get("Content-Type", "")
            return (resp.status,
                    json.loads(raw) if "json" in ctype else raw.decode(),
                    dict(resp.headers))
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null"), dict(e.headers)


class TestEngineServerPhases:
    def test_unbatched_phases_recorded(self):
        qs, srv = _deploy_synthetic(batching=False)
        try:
            for i in range(5):
                status, body, headers = _call(
                    srv.port, "POST", "/queries.json",
                    {"user": f"u{i}", "num": 3})
                assert status == 200
                assert headers.get("X-Request-ID")
            status, st, _ = _call(srv.port, "GET", "/status.json")
            assert status == 200
            phases = st["phases"]
            for phase in ("phase=assemble", "phase=supplement",
                          "phase=dispatch", "phase=serve",
                          "phase=readback"):
                assert phases[phase]["count"] >= 5, phases.keys()
                assert phases[phase]["p99"] is not None
            assert st["latency"]["count"] >= 5
            assert st["transferGuardViolations"] >= 0
            assert isinstance(st["hbm"], list)  # empty on CPU: graceful
            status, text, _ = _call(srv.port, "GET", "/metrics")
            assert status == 200
            validate_exposition(text)
            assert 'pio_query_phase_seconds_bucket{phase="dispatch"' \
                in text
            assert "pio_query_latency_seconds_count 5" in text
            assert "pio_compiles_since_warm" in text
        finally:
            srv.shutdown()

    def test_batched_phases_queue_and_occupancy(self):
        qs, srv = _deploy_synthetic(batching=True)
        try:
            results = [None] * 8

            def fire(i):
                results[i] = _call(srv.port, "POST", "/queries.json",
                                   {"user": f"u{i}", "num": 3})

            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert all(r[0] == 200 for r in results)
            status, st, _ = _call(srv.port, "GET", "/status.json")
            assert st["phases"]["phase=queue_wait"]["count"] >= 8
            assert st["batchOccupancy"]["count"] >= 1
            assert st["queueDepth"]["count"] >= 1
            # 8 concurrent queries over max_batch=8: every query was
            # coalesced, so total occupancy-weighted count is 8
            status, text, _ = _call(srv.port, "GET", "/metrics")
            validate_exposition(text)
            assert "pio_batch_occupancy_count" in text
            assert "pio_queue_depth_count" in text
            assert 'pio_query_phase_seconds_bucket{phase="queue_wait"' \
                in text
        finally:
            srv.shutdown()

    def test_direct_query_records_without_http(self):
        qs, srv = _deploy_synthetic(batching=False)
        try:
            obs = {}
            qs.query({"user": "u1", "num": 2}, obs=obs)
            assert "dispatchMs" in obs and "serveMs" in obs
            assert qs.spans_summary()["query (end-to-end)"]["count"] == 1
        finally:
            srv.shutdown()

    def test_query_errors_counted(self):
        qs, srv = _deploy_synthetic(batching=False)
        try:
            status, _, _ = _call(srv.port, "POST", "/queries.json",
                                 {"bogus": 1})
            assert status == 400
            snap = qs.metrics.snapshot()
            assert snap["pio_query_errors_total"]["status=400"] == 1
        finally:
            srv.shutdown()

    def test_access_log_line_carries_request_id_and_phases(self):
        records = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        access = logging.getLogger("predictionio_tpu.access")
        handler = Capture()
        old_level = access.level
        access.addHandler(handler)
        access.setLevel(logging.INFO)
        qs, srv = _deploy_synthetic(batching=False)
        try:
            status, _, headers = _call(srv.port, "POST", "/queries.json",
                                       {"user": "u1", "num": 2})
            assert status == 200
            lines = [json.loads(r) for r in records]
            mine = [ln for ln in lines
                    if ln.get("path") == "/queries.json"]
            assert mine, "no access-log line for the query"
            line = mine[-1]
            assert line["requestId"] == headers["X-Request-ID"]
            assert line["status"] == 200
            assert "dispatchMs" in line and "durationMs" in line
        finally:
            srv.shutdown()
            access.removeHandler(handler)
            access.setLevel(old_level)


# ---------------------------------------------------------------------------
# process self-telemetry + scrape self-cost (ISSUE 17 satellites)
# ---------------------------------------------------------------------------

class TestProcessMetrics:
    def test_process_stats_sane(self):
        from predictionio_tpu.obs import process_stats

        st = process_stats()
        if not st:
            pytest.skip("/proc not readable on this platform")
        assert st["rss_bytes"] > (1 << 20)
        assert st["cpu_seconds_total"] > 0.0
        assert st["open_fds"] >= 3
        assert st["threads"] >= 1

    def test_process_gauges_render(self):
        from predictionio_tpu.obs import (
            process_stats,
            register_process_metrics,
        )

        reg = MetricsRegistry()
        register_process_metrics(reg)
        if not process_stats():
            return  # no-op registration off Linux: nothing to assert
        text = reg.render()
        validate_exposition(text)
        for name in ("pio_process_rss_bytes",
                     "pio_process_cpu_seconds_total",
                     "pio_process_open_fds", "pio_process_threads"):
            assert re.search(rf"^{name} [0-9.e+]+$", text,
                             re.MULTILINE), name


def _burn(seconds):
    import time as _time

    t0 = _time.thread_time()
    while _time.thread_time() - t0 < seconds:
        pass


def _run_to_its_tasks_end(name, seconds):
    """Burn ``seconds`` of CPU on a thread called ``name``, join it, and
    wait until its OS task is gone too: a pass that finds it still
    there sees a task Python no longer knows."""
    import os
    import time as _time

    tid = []

    def run():
        tid.append(threading.get_native_id())
        _burn(seconds)

    thread = threading.Thread(target=run, name=name)
    thread.start()
    thread.join(30)
    deadline = _time.monotonic() + 10
    while os.path.exists(f"/proc/self/task/{tid[0]}") \
            and _time.monotonic() < deadline:
        _time.sleep(0.001)


@contextlib.contextmanager
def _live_thread_that_burned(name, seconds):
    """A thread called ``name`` that has burned ``seconds`` of CPU and
    is still alive (asleep) inside the block."""
    stop, burned = threading.Event(), threading.Event()

    def work():
        _burn(seconds)
        burned.set()
        stop.wait(30)

    t = threading.Thread(target=work, name=name)
    t.start()
    try:
        assert burned.wait(30)
        yield
    finally:
        stop.set()
        t.join(30)


def _thread_seconds(export, state="cpu"):
    return {c["labels"]["role"]: c["value"]
            for c in export["pio_thread_seconds_total"]["children"]
            if c["labels"]["state"] == state}


@pytest.fixture
def host_registry():
    from predictionio_tpu.obs import register_process_metrics

    reg = MetricsRegistry()
    register_process_metrics(reg)
    if reg.get("pio_thread_seconds_total") is None:
        pytest.skip("/proc/self/task not readable on this platform")
    return reg


class TestHostClocks:
    """The host's seconds by thread role (ISSUE 37): one pass over
    /proc/self/task per export, every server thread under a role."""

    def test_every_engine_server_thread_has_a_role(self, monkeypatch):
        import http.client

        from conftest import serve_staged_batch

        from predictionio_tpu.obs.runtime import thread_role
        from predictionio_tpu.workflow import batch_predict

        # a pool of this test's own: an earlier test's idle threads
        # would take the submissions and no new thread would show
        monkeypatch.setattr(batch_predict, "_dispatch_pool", None)
        before = set(threading.enumerate())
        qs, srv = _deploy_synthetic(batching=True)

        class Supplementing(type(qs.serving)):
            """The shipped serving inherits ``Serving.supplement``,
            which never reaches the pool (ISSUE 46): an override does."""

            def supplement(self, query):
                return query

        qs.serving = Supplementing()
        conns = [http.client.HTTPConnection("127.0.0.1", srv.port,
                                            timeout=60) for _ in range(6)]
        try:
            def fire(conn, i):
                conn.request("POST", "/queries.json", json.dumps(
                    {"user": f"u{i}", "num": 3}))
                assert conn.getresponse().read()

            # keep-alive, so that the handler threads are still there
            # to be looked at
            clients = [threading.Thread(target=fire, args=(c, i))
                       for i, c in enumerate(conns)]
            for t in clients:
                t.start()
            for t in clients:
                t.join(60)
            # one batch that holds several queries whatever the load
            # made of the six above, so that the supplement pool starts
            serve_staged_batch(qs, [{"user": f"u{i}", "num": 3}
                                    for i in range(3)])
            roles = {}
            for t in set(threading.enumerate()) - before:
                roles.setdefault(thread_role(t.name), set()).add(t.name)
            # the SLO evaluator ticks beside the query path; every
            # thread ON it has a role of its own
            assert roles.pop("other", set()) <= {"slo-engine"}
            assert set(roles) == {"handler", "acceptor", "assemble",
                                  "dispatch", "readback", "supplement"}
            assert len(roles["handler"]) == 1  # all called http-handler
            counts = {c["labels"]["role"]: c["value"] for c in
                      qs.metrics.export()["pio_thread_count"]["children"]}
            assert counts["handler"] == len(conns)
            assert counts["acceptor"] == 1 and counts["assemble"] >= 1
            assert counts["other"] >= 1  # the main thread
        finally:
            for c in conns:
                c.close()
            srv.shutdown()
            qs.close()

    def test_os_thread_name_is_the_roles(self):
        from predictionio_tpu.obs.runtime import RoleThread, name_os_thread

        comm = {}

        def read_comm(key):
            with open(f"/proc/self/task/{threading.get_native_id()}"
                      f"/comm") as f:
                comm[key] = f.read().strip()

        def handler():
            name_os_thread("http-handler")  # what _Handler.setup does
            read_comm(threading.current_thread().name)

        threads = [
            RoleThread(target=read_comm, args=("dispatch",),
                       name="pipeline-dispatch-3"),
            RoleThread(target=read_comm, args=("other",),
                       name="slo-engine"),
            threading.Thread(target=handler)]
        try:
            for t in threads:
                t.start()
        finally:
            for t in threads:
                t.join(10)
        if not comm:
            pytest.skip("/proc/self/task not readable on this platform")
        with open("/proc/self/comm") as f:
            process = f.read().strip()
        assert comm == {"dispatch": "pio-dispatch", "other": process,
                        "http-handler": "pio-handler"}

    def test_cpu_children_sum_to_the_process(self, host_registry):
        import time as _time

        def total(export):
            return sum(_thread_seconds(export).values())

        _burn(0.2)
        a = host_registry.export()
        assert total(a) == pytest.approx(_time.process_time(), rel=0.02)
        assert a["pio_process_cpu_seconds_total"]["children"][0][
            "value"] == pytest.approx(total(a), rel=0.02, abs=0.02)
        # a thread no pass ever saw: the exited child takes it
        _run_to_its_tasks_end("pipeline-readback-9", 0.2)
        b = host_registry.export()
        assert total(b) == pytest.approx(_time.process_time(), rel=0.02)
        grew = {r: v - _thread_seconds(a)[r]
                for r, v in _thread_seconds(b).items()}
        assert grew["exited"] == pytest.approx(0.2, abs=0.05)
        assert grew["readback"] == 0.0

    def test_a_role_keeps_what_a_pass_saw_when_its_thread_exits(
            self, host_registry):
        # a role's seconds only grow: what a handler burned before its
        # connection closed stays the handlers'
        a = host_registry.export()
        with _live_thread_that_burned("http-handler", 0.2):
            b = host_registry.export()
        c = host_registry.export()
        handler = [_thread_seconds(e)["handler"] for e in (a, b, c)]
        assert handler[1] - handler[0] == pytest.approx(0.2, abs=0.05)
        assert handler[2] >= handler[1]

    def test_a_busy_dispatch_thread_moves_its_role_alone(
            self, host_registry):
        a = host_registry.export()
        with _live_thread_that_burned("pipeline-dispatch-0", 0.3):
            b = host_registry.export()
        grew = {r: v - _thread_seconds(a)[r]
                for r, v in _thread_seconds(b).items()}
        assert grew.pop("dispatch") == pytest.approx(0.3, abs=0.05)
        assert grew.pop("other") < 0.2   # this test's own thread
        assert grew.pop("native") < 0.2
        # the process's clock is read after the tasks': what ran
        # meanwhile is the pass's own few hundred microseconds
        assert grew.pop("exited") < 0.01
        assert all(v == 0.0 for v in grew.values()), grew
        assert set(_thread_seconds(b, "runqueue")) \
            == set(grew) | {"dispatch", "other", "native"}

    def test_without_schedstat_the_threads_cpu_clocks_are_read(
            self, monkeypatch):
        # a kernel without CONFIG_SCHED_INFO, or a sandboxed one (the
        # chip machine's): no file a task, no run-queue children, the
        # same roles and the same sum
        import time as _time

        from predictionio_tpu.obs import register_process_metrics, runtime

        def no_schedstat(path):
            if path.endswith("/schedstat"):
                raise FileNotFoundError(path)
            return runtime._read(path)

        monkeypatch.setattr(runtime, "_lock_held_reader",
                            lambda: no_schedstat)
        reg = MetricsRegistry()
        register_process_metrics(reg)
        a = reg.export()
        if "pio_thread_seconds_total" not in a:
            pytest.skip("/proc/self/task not readable on this platform")
        assert _thread_seconds(a, "runqueue") == {}
        _run_to_its_tasks_end("pipeline-readback-9", 0.1)
        with _live_thread_that_burned("pipeline-dispatch-0", 0.2):
            b = reg.export()
        grew = {r: v - _thread_seconds(a)[r]
                for r, v in _thread_seconds(b).items()}
        assert grew["dispatch"] == pytest.approx(0.2, abs=0.05)
        assert grew["exited"] == pytest.approx(0.1, abs=0.05)
        assert sum(_thread_seconds(b).values()) == pytest.approx(
            _time.process_time(), rel=0.02)

    def test_one_export_makes_one_pass(self, host_registry, monkeypatch):
        import os

        from predictionio_tpu.obs import runtime

        listed = []
        listdir = os.listdir

        def counting(path="."):
            listed.append(path)
            return listdir(path)

        monkeypatch.setattr(runtime.os, "listdir", counting)
        host_registry.export()
        assert listed.count(runtime._TASKS) == 1
        host_registry.render()
        host_registry.snapshot()
        assert listed.count(runtime._TASKS) == 3
        # and the four pio_process_* gauges share the pass's one read
        assert listed.count("/proc/self/fd") == 3

    def test_without_proc_self_task_the_families_are_absent(
            self, monkeypatch):
        from predictionio_tpu.obs import (
            process_stats,
            register_process_metrics,
            runtime,
        )

        if not process_stats():
            pytest.skip("/proc not readable on this platform")
        monkeypatch.setattr(runtime, "_TASKS", "/proc/self/no-such-dir")
        reg = MetricsRegistry()
        register_process_metrics(reg)
        export = reg.export()
        assert "pio_process_cpu_seconds_total" in export
        for name in ("pio_thread_seconds_total", "pio_thread_count",
                     "pio_host_cpus"):
            assert name not in export
        validate_exposition(reg.render())

    def test_a_failing_hook_does_not_fail_the_export(self):
        reg = MetricsRegistry()
        reg.gauge("t_plain").set(1.0)
        calls = []

        def hook():
            calls.append(1)
            raise OSError("no /proc today")

        reg.before_collect(hook)
        assert reg.export()["t_plain"]["children"][0]["value"] == 1.0
        assert "t_plain 1" in reg.render()
        assert reg.snapshot()["t_plain"] == 1.0
        assert len(calls) == 3


class TestScrapeSelfCost:
    def test_10k_series_render_under_budget(self):
        # the scrape self-cost guard (ISSUE 17): a registry an order
        # of magnitude wider than the engine server's must still
        # render in a small fraction of the fleet scrape interval —
        # rendering itself must never be the serving regression
        import time as _time

        reg = MetricsRegistry()
        wide = reg.gauge("t_wide_series", "one child per shard")
        for i in range(10_000):
            wide.labels(shard=str(i)).set(float(i))
        t0 = _time.perf_counter()
        text = reg.render()
        elapsed = _time.perf_counter() - t0
        assert text.count("\n") >= 10_000
        assert elapsed < 2.0, f"10k-series render took {elapsed:.2f}s"
        t0 = _time.perf_counter()
        reg.export()
        assert _time.perf_counter() - t0 < 2.0

    def test_render_seconds_histogram_on_metrics_routes(self):
        # every /metrics(.json) render observes its own wall time, by
        # format — the self-cost series the fleet plane watches
        qs, srv = _deploy_synthetic(batching=False)
        try:
            status, text, _ = _call(srv.port, "GET", "/metrics")
            assert status == 200
            # a render observes itself AFTER snapshotting, so the
            # first JSON scrape can't contain its own timing — read
            # the second
            _call(srv.port, "GET", "/metrics.json")
            status, export, _ = _call(srv.port, "GET", "/metrics.json")
            assert status == 200
            fam = export["pio_metrics_render_seconds"]
            assert fam["kind"] == "histogram"
            by_format = {c["labels"]["format"]: c["count"]
                         for c in fam["children"]}
            assert by_format.get("text", 0) >= 1
            assert by_format.get("json", 0) >= 1
        finally:
            srv.shutdown()

    def test_metrics_json_export_matches_text_exposition(self):
        qs, srv = _deploy_synthetic(batching=False)
        try:
            _call(srv.port, "POST", "/queries.json",
                  {"user": "u1", "num": 2})
            status, export, _ = _call(srv.port, "GET", "/metrics.json")
            assert status == 200
            lat = export["pio_query_latency_seconds"]["children"][0]
            assert lat["count"] == 1
            assert lat["buckets"][-1][0] == "+Inf"
            assert lat["buckets"][-1][1] == 1
            # counters carry plain values
            total = export["pio_http_requests_total"]["children"]
            assert any(c["labels"].get("route") == "/queries.json"
                       and c["value"] >= 1 for c in total)
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# event + storage server exposition
# ---------------------------------------------------------------------------

class TestEventServerMetrics:
    @pytest.fixture()
    def served(self):
        from predictionio_tpu.data.storage import AccessKey, App, Storage
        from predictionio_tpu.server.eventserver import (
            create_event_server,
        )

        storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
        app_id = storage.apps().insert(App(0, "obsev"))
        storage.access_keys().insert(
            AccessKey(key="KEY", app_id=app_id, events=()))
        storage.events().init(app_id)
        srv = create_event_server(storage, host="127.0.0.1", port=0)
        srv.start_background()
        yield srv
        srv.shutdown()

    def test_metrics_and_status(self, served):
        ev = {"event": "rate", "entityType": "user", "entityId": "u1",
              "targetEntityType": "item", "targetEntityId": "i1",
              "properties": {"rating": 5}}
        status, body, _ = _call(served.port, "POST",
                                "/events.json?accessKey=KEY", ev)
        assert status == 201
        status, st, _ = _call(served.port, "GET", "/status.json")
        assert status == 200
        assert st["statsEnabled"] is False
        assert st["metrics"]["pio_stats_enabled"] == 0
        assert st["metrics"]["pio_events_ingested_total"][
            "route=events"] == 1
        status, text, _ = _call(served.port, "GET", "/metrics")
        assert status == 200
        validate_exposition(text)
        assert 'pio_events_ingested_total{route="events"} 1' in text
        assert "pio_stats_enabled 0" in text
        # event-ingest latency histogram (the acceptance criterion's
        # "event latency" series) exists for the /events.json route
        assert 'pio_http_request_duration_seconds_bucket' in text
        assert 'route="/events.json"' in text

    def test_stats_404_explains_flag(self, served):
        status, body, _ = _call(served.port, "GET",
                                "/stats.json?accessKey=KEY")
        assert status == 404
        assert "--stats" in body["message"]
        assert body["statsEnabled"] is False
        assert "hint" in body


class TestStorageServerMetrics:
    def test_columnar_hit_miss_counters(self, tmp_path):
        from tests.conftest import start_sqlite_backed_storage_server

        srv, backing = start_sqlite_backed_storage_server(tmp_path)
        try:
            from predictionio_tpu.data.event import Event
            from predictionio_tpu.data.storage import App

            app_id = backing.apps().insert(App(0, "obsst"))
            backing.events().init(app_id)
            backing.events().insert(
                Event(event="rate", entity_type="user", entity_id="u1",
                      target_entity_type="item", target_entity_id="i1",
                      properties={"rating": 4.0}), app_id)
            url = (f"http://127.0.0.1:{srv.port}"
                   f"/v1/events/{app_id}/columnar")
            with urllib.request.urlopen(url, timeout=30) as resp:
                etag = resp.headers["ETag"]
                assert resp.status == 200
            req = urllib.request.Request(
                url, headers={"If-None-Match": etag})
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    assert resp.status == 304
            except urllib.error.HTTPError as e:
                assert e.code == 304
            status, text, _ = _call(srv.port, "GET", "/metrics")
            assert status == 200
            validate_exposition(text)
            assert 'pio_columnar_requests_total{outcome="miss"} 1' \
                in text
            assert 'pio_columnar_requests_total{outcome="hit"} 1' \
                in text
            m = re.search(r"^pio_columnar_bytes_total (\d+)$", text,
                          re.MULTILINE)
            assert m and int(m.group(1)) > 0
            status, st, _ = _call(srv.port, "GET", "/status.json")
            assert st["status"] == "alive"
        finally:
            srv.shutdown()


class TestDashboardMetrics:
    def test_dashboard_mounts_metrics_and_table(self):
        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.server.dashboard import create_dashboard

        storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
        srv = create_dashboard(storage, host="127.0.0.1", port=0)
        srv.start_background()
        try:
            status, html, _ = _call(srv.port, "GET", "/")
            assert status == 200
            # second hit: the first request is now in the registry, so
            # the index renders its percentile table
            status, html, _ = _call(srv.port, "GET", "/")
            assert "Request latency percentiles" in html
            status, text, _ = _call(srv.port, "GET", "/metrics")
            assert status == 200
            validate_exposition(text)
            assert "pio_http_request_duration_seconds_bucket" in text
        finally:
            srv.shutdown()
