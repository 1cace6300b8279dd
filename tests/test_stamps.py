"""One stamped timeline per query and per batch (ISSUE 24): a query's
phases and HTTP parts add up to its residence, the starvation clock's
five states add up to the wall time, the flight recorder shows the
stamps themselves, and a profiler capture holds the ``pio:`` stages on
the host plane."""

import glob
import http.client
import json
import threading
import time

import pytest

from predictionio_tpu.obs import MetricsRegistry, OverlapTracker
from predictionio_tpu.obs.overlap import STATES
from predictionio_tpu.server.engineserver import (
    ServerConfig,
    create_engine_server,
)
from test_pipeline import _mk_server

#: a staged query's residence, in the order it is lived
QUERY_PHASES = ("admit", "queue_wait", "assemble", "supplement",
                "dispatch_q", "dispatch", "readback_q", "device_wait",
                "serve", "finish", "wake", "respond")
CLOSE = 50e-6  # seconds


@pytest.fixture(scope="module")
def served():
    """A real ``QueryServer`` with batching on behind its HTTP server;
    every request's stamp record is kept as the server lets go of it
    (the closing stamps are taken on the handler thread a moment after
    the client has its answer, so the tests wait for the record)."""
    qs = _mk_server(ServerConfig(batching=True, max_batch=8,
                                 batch_window_ms=2.0, warm_start=False,
                                 trace_slow_ms=0.001))
    srv = create_engine_server(qs, host="127.0.0.1", port=0)
    records, cond = [], threading.Condition()
    sent = srv.app.sent

    def keep(req):
        sent(req)
        with cond:
            records.append(req)
            cond.notify_all()

    srv.app.sent = keep
    srv.start_background()

    def records_after(n):
        with cond:
            assert cond.wait_for(lambda: len(records) >= n, timeout=30)
            return list(records)

    yield qs, srv, records_after
    srv.shutdown()
    qs.close()


def _post(conn, user):
    conn.request("POST", "/queries.json",
                 json.dumps({"user": user, "num": 3}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    assert resp.status == 200, body
    return dict(resp.getheaders())


def _burst(port, clients=6, each=4):
    def fire(i):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        for _ in range(each):
            _post(conn, f"u{i}")
        conn.close()

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    return clients * each


def _timeline(st):
    """``[(part, start, end)]`` of a served query, from its own record
    and its batch's: what every exported series is derived from."""
    t_pick = st.batch.spans[0][1]
    return ([("read", st.t_recv, st.t_enter),
             ("admit", st.t_enter, st.t_enq),
             ("queue_wait", st.t_enq, t_pick)]
            + list(st.batch.spans)
            + [("wake", st.t_done, st.t_wake),
               ("respond", st.t_wake, st.t_return),
               ("write", st.t_return, st.t_sent)])


def _span(timeline, phase):
    return next((t0, t1) for name, t0, t1 in timeline.spans
                if name == phase)


class TestClosure:
    def test_every_query_closes_and_every_stamp_is_monotone(self, served):
        qs, srv, records_after = served
        before = len(records_after(0))
        n = _burst(srv.port)
        mine = [r.stamps for r in records_after(before + n)[before:]]
        assert len(mine) == n
        sizes = set()
        for st in mine:
            assert st.batch is not None
            assert st.t_done == st.batch.spans[-1][2]  # the batch's
            sizes.add(st.batch.n)
            parts = _timeline(st)
            assert [p[0] for p in parts[1:-1]] == list(QUERY_PHASES)
            # each part ends where the next one starts, none is negative
            for (_, _, end), (_, start, _) in zip(parts, parts[1:]):
                assert end == start
            assert all(t1 >= t0 for _, t0, t1 in parts), parts
            residence = st.t_sent - st.t_recv
            assert abs(sum(t1 - t0 for _, t0, t1 in parts)
                       - residence) < CLOSE
        assert max(sizes) > 1, "the burst never coalesced"

    def test_exported_phases_sum_to_the_exported_residence(self, served):
        """One query at a time, so each series moves by that query
        alone: the increase of the phase sums plus the two HTTP parts
        is the increase of the residence sum."""
        qs, srv, records_after = served

        def sums():
            ex = qs.metrics.export()

            def children(name, key):
                return {c["labels"][key]: (c["sum"], c["count"])
                        for c in ex[name]["children"]}

            return (children("pio_query_phase_seconds", "phase"),
                    children("pio_http_io_seconds", "part"),
                    children("pio_http_residence_seconds", "route"),
                    ex["pio_query_latency_seconds"]["children"][0])

        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        done = len(records_after(0))
        _post(conn, "u1")  # every series exists from here on
        done += 1
        records_after(done)
        for user in ("u2", "u3", "u4"):
            ph0, io0, res0, lat0 = sums()
            _post(conn, user)
            done += 1
            st = records_after(done)[-1].stamps
            ph1, io1, res1, lat1 = sums()
            grew = {p: ph1[p][0] - ph0[p][0] for p in QUERY_PHASES}
            assert all(ph1[p][1] - ph0[p][1] == 1 for p in QUERY_PHASES)
            parts = sum(grew.values()) + sum(
                io1[p][0] - io0[p][0] for p in ("read", "write"))
            residence = (res1["/queries.json"][0]
                         - res0["/queries.json"][0])
            assert residence == pytest.approx(st.t_sent - st.t_recv,
                                              abs=1e-9)
            assert abs(parts - residence) < CLOSE
            # the series that were there before read the same record
            assert lat1["sum"] - lat0["sum"] == pytest.approx(
                st.t_done - st.t_enq, abs=1e-9)
            t0, t1 = _span(st.batch, "device_wait")
            assert grew["device_wait"] == pytest.approx(t1 - t0, abs=1e-9)
        conn.close()

    def test_stage_series_read_the_same_record(self, served):
        qs, srv, records_after = served

        def stage_sums():
            ex = qs.metrics.export()["pio_pipeline_stage_seconds"]
            return {c["labels"]["stage"]: c["sum"]
                    for c in ex["children"]}

        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        done = len(records_after(0))
        s0 = stage_sums()
        _post(conn, "u5")
        ab = records_after(done + 1)[-1].stamps.batch
        conn.close()
        s1 = stage_sums()
        want = {  # each stage thread's work, hand-off waits left out
            "assemble": _span(ab, "supplement")[1] - _span(ab, "assemble")[0],
            "dispatch": _span(ab, "dispatch")[1] - _span(ab, "dispatch")[0],
            "readback": _span(ab, "finish")[1] - _span(ab, "device_wait")[0]}
        for stage, sec in want.items():
            assert s1[stage] - s0[stage] == pytest.approx(sec, abs=1e-9)

    def test_a_request_handled_without_a_socket_observes_no_io(self):
        """``HTTPApp.handle`` called directly (tests, embedding) has
        no ``t_recv``: it stamps what it lives through and the
        socket-side series stay silent."""
        from predictionio_tpu.server.http import (
            HTTPApp,
            Request,
            json_response,
            mount_metrics,
        )

        app = HTTPApp("toy")
        reg = MetricsRegistry()
        mount_metrics(app, reg, runtime=False)

        @app.route("GET", "/x")
        def x(req):
            return json_response({"ok": True})

        req = Request("GET", "/x", {}, {}, b"")
        assert app.handle(req).status == 200
        st = req.stamps
        assert st.t_recv is None and st.t_sent is None
        assert st.t_enter <= st.t_return
        assert req.obs["_stamps"] is st and st.route == "/x"
        ex = reg.export()
        assert [c["count"] for c in
                ex["pio_http_io_seconds"]["children"]] == [0, 0]
        assert ex["pio_http_residence_seconds"]["children"] == []


class TestFlightRecorderShowsTheStamps:
    def test_spans_of_a_staged_query_are_its_stamps(self, served):
        """Nothing is laid end to end from durations: every span of
        the retained trace starts and ends on a stamp of the record."""
        qs, srv, records_after = served
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        done = len(records_after(0))
        headers = _post(conn, "u6")
        conn.close()
        assert headers.get("X-Trace-Retained") == "slow"
        req = records_after(done + 1)[-1]
        st, ab = req.stamps, req.stamps.batch
        trace = qs.tracer.recorder.get(req.trace.trace_id)
        got = {s.name: (s.t_start, s.t_end) for s in trace.spans}
        want = {name: (t0, t1) for name, t0, t1 in ab.spans}
        t_pick, t_done = ab.spans[0][1], ab.spans[-1][2]
        want.update(batch=(t_pick, t_done),
                    queue_wait=(st.t_enq, t_pick),
                    admit=(st.t_enter, st.t_enq),
                    wake=(st.t_done, st.t_wake))
        readback = got.pop("readback")
        assert got == want  # exact: the same floats
        # the query's own serialisation lies inside ``finish``
        finish = _span(ab, "finish")
        assert finish[0] <= readback[0] <= readback[1] <= finish[1]
        batch = next(s for s in trace.spans if s.name == "batch")
        assert batch.attrs["batch"] == ab.seq
        assert batch.attrs["batchSize"] == ab.n
        for s in trace.spans:
            assert s.parent_id == (
                trace.root_span_id if s.name in ("batch", "admit", "wake")
                else batch.span_id), s.name


class TestStarvationClock:
    def _clock(self):
        t = [0.0]
        return t, OverlapTracker(time_fn=lambda: t[0])

    def test_scripted_overlapping_batches(self):
        """Two batches, A ahead of B, stamped as the pipeline stamps
        them; the state in force between two transitions is the first
        of the priority order that holds a batch."""
        _, tr = self._clock()
        script = [
            # (at, step arguments, state in force AFTER it)
            (10.0, dict(enter="assemble", join="assembling"),
             "assembling"),                                     # A picked
            (11.0, dict(enter="assemble", join="assembling"),
             "assembling"),                                     # B picked
            (13.0, dict(exit="assemble", leave="assembling",
                        join="staged"), "staged"),              # A staged
            (14.0, dict(enter="device", leave="staged",
                        join="launching"), "launching"),        # A launch
            (16.0, dict(exit="assemble", leave="assembling",
                        join="staged"), "launching"),           # B staged
            (17.0, dict(leave="launching", join="enqueued"),
             "enqueued"),                                       # A queued
            (18.0, dict(enter="device", leave="staged",
                        join="launching"), "enqueued"),         # B launch
            (20.0, dict(leave="enqueued"), "launching"),        # A ready
            (21.0, dict(exit="device", enter="readback"),
             "launching"),                                      # A served
            (23.0, dict(leave="launching", join="enqueued"),
             "enqueued"),                                       # B queued
            (24.0, dict(exit="readback"), "enqueued"),          # A done
            (27.0, dict(leave="enqueued"), "empty"),            # B ready
            (28.0, dict(exit="device", enter="readback"), "empty"),
            (30.0, dict(exit="readback"), "empty"),             # B done
        ]
        want = dict.fromkeys(STATES, 0.0)
        for (at, kw, state), nxt in zip(script, script[1:] + [None]):
            tr.step(at, **kw)
            if nxt is not None:
                want[state] += nxt[0] - at
            snap = tr.snapshot()  # the fake clock still reads 0: no
            # time is folded in past the last stamp
            assert sum(snap["state_sec"].values()) == pytest.approx(
                snap["wall_sec"])
        assert want == {"enqueued": 7.0, "launching": 6.0, "staged": 1.0,
                        "assembling": 3.0, "empty": 3.0}
        snap = tr.snapshot()
        assert snap["state_sec"] == pytest.approx(want)
        assert snap["wall_sec"] == pytest.approx(20.0)
        # the tracks beside it kept their meaning: device from the
        # launch (14) to B served (28), overlap where a host track ran
        assert snap["device_busy_sec"] == pytest.approx(14.0)
        assert snap["busy_sec"]["assemble"] == pytest.approx(6.0)
        assert snap["busy_sec"]["readback"] == pytest.approx(5.0)

    @pytest.mark.parametrize("holding,state", [
        (("enqueued", "launching", "staged", "assembling"), "enqueued"),
        (("launching", "staged", "assembling"), "launching"),
        (("staged", "assembling"), "staged"),
        (("assembling",), "assembling"),
        ((), "empty"),
    ])
    def test_priority_order(self, holding, state):
        t, tr = self._clock()
        tr.step(1.0)  # the first transition opens the clock
        for s in holding:
            tr.step(1.0, join=s)
        t[0] = 5.0
        sec = tr.snapshot()["state_sec"]
        assert sec[state] == pytest.approx(4.0)
        assert sum(sec.values()) == pytest.approx(4.0)

    def test_late_stamps_never_turn_the_clock_back(self):
        """Stamps are taken outside the tracker's lock, so two threads'
        transitions can arrive out of order: the states still sum to
        the wall time and none goes negative."""
        _, tr = self._clock()
        tr.step(1.0, enter="assemble", join="assembling")
        tr.step(2.0, enter="device", join="launching")
        tr.step(1.5, exit="assemble", leave="assembling")  # late
        tr.step(3.0, exit="device", leave="launching")
        snap = tr.snapshot()
        assert snap["wall_sec"] == pytest.approx(2.0)
        assert sum(snap["state_sec"].values()) == pytest.approx(2.0)
        assert min(snap["state_sec"].values()) >= 0.0
        assert tr.state_seconds("launching") == pytest.approx(1.0)

    def test_exported_states_sum_to_the_wall_time(self, served):
        qs, srv, records_after = served
        before = len(records_after(0))
        n = _burst(srv.port, clients=4, each=3)
        records_after(before + n)
        fam = qs.metrics.export()["pio_pipeline_state_seconds_total"]
        assert fam["kind"] == "counter"
        secs = {c["labels"]["state"]: c["value"]
                for c in fam["children"]}
        assert set(secs) == set(STATES)
        assert all(v >= 0.0 for v in secs.values())
        # each child folds in time up to its own read: microseconds
        assert sum(secs.values()) == pytest.approx(
            qs.overlap.snapshot()["wall_sec"], abs=0.05)
        assert secs["enqueued"] > 0.0


class TestBenchmarkReaders:
    def test_new_metrics_read_a_real_servers_exports(self, served):
        """The four new per-layer metrics of each cell, read by the
        benchmark's own readers from two exports of a real server."""
        from cellbench import manifest, readers

        qs, srv, records_after = served
        before = len(records_after(0))
        first = qs.metrics.export()
        n = _burst(srv.port, clients=4, each=3)
        records_after(before + n)
        facts = {"registry": (first, qs.metrics.export())}
        for stem in ("device_wait_ms", "http_overhead_ms",
                     "host_starved_pct", "starved_empty_pct"):
            for suffix in (".steady", ".sat"):
                spec = manifest.read_json(
                    manifest.metric_path(stem + suffix))
                value = readers.read(facts, spec)
                assert value is not None, stem + suffix
                if stem.endswith("_pct"):
                    assert 0.0 <= value <= 100.0


class TestProfilerHoldsTheStages:
    def test_capture_holds_pio_annotations_on_the_host_plane(
            self, served, tmp_path):
        """A 0.3 s capture with the Python tracer off around a few
        batched queries: the stages lie on the host plane with the
        batch's number and size, the dispatch thread's on another line
        than the readback thread's."""
        import jax

        qs, srv, records_after = served
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        done = len(records_after(0))
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            t0 = time.monotonic()
            # six rounds, so more batches than dispatch threads: one of
            # them begins AND ends a wait inside the capture
            n = _burst(srv.port, clients=3, each=6)
            records_after(done + n)
            time.sleep(max(0.3 - (time.monotonic() - t0), 0.0))
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(
            tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        host, = [p for p in data.planes if p.name == "/host:CPU"]
        lines_of, stats_of = {}, {}
        for i, line in enumerate(host.lines):
            for e in line.events:
                if e.name.startswith("pio:"):
                    lines_of.setdefault(e.name, set()).add(i)
                    stats_of.setdefault(e.name, []).append(
                        dict(e.stats))
        for name in ("pio:assemble", "pio:supplement", "pio:dispatch",
                     "pio:device_wait", "pio:serve", "pio:finish",
                     "pio:wait_dispatch_q", "pio:http_read",
                     "pio:http_write"):
            assert name in lines_of, sorted(lines_of)
        seqs = {r.stamps.batch.seq
                for r in records_after(done + n)[done:]}
        for name in ("pio:dispatch", "pio:device_wait",
                     "pio:wait_dispatch_q"):
            for stats in stats_of[name]:
                assert stats["n"] >= 1 and stats["batch"] >= 1, stats
            assert {s["batch"] for s in stats_of[name]} & seqs
        assert not lines_of["pio:dispatch"] & lines_of["pio:device_wait"]
