"""Minimal threaded HTTP app framework shared by the framework's servers.

The reference runs three akka-http servers (Event Server
``data/api/EventServer.scala``, engine server ``workflow/CreateServer.scala``,
admin/dashboard ``tools/``). Here one stdlib-based micro-framework backs all
of them: regex-routed handlers over ``ThreadingHTTPServer`` — no actor
system, no external dependencies, good enough for host-side control planes
(the TPU data plane never goes through HTTP).
"""

from __future__ import annotations

import json
import logging
import os
import random
import re
import sys
import threading
import time
from dataclasses import dataclass, field
from http import HTTPStatus
from http.server import ThreadingHTTPServer
from socketserver import StreamRequestHandler
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from ..concurrency import new_lock
from ..data.storage.base import StorageError
from ..faults import FaultError
from ..obs.runtime import RoleThread, name_os_thread
from ..obs.trace import stage_span

__all__ = ["Request", "RequestStamps", "Response", "HTTPApp", "AppServer",
           "json_response", "mount_metrics", "mount_trace_routes"]

#: Retry-After seconds on a 503 caused by an unavailable backing store
#: (docs/reliability.md): short enough that a recovered store is back
#: in rotation fast, long enough that a retrying client is not the one
#: that keeps it down
RETRY_AFTER_SECONDS = 1

#: Structured JSON access log — one line per request with the request id
#: and any per-phase timings the handler attached (``Request.obs``).
#: Quiet unless the operator enables INFO on this logger.
access_log = logging.getLogger("predictionio_tpu.access")


class RequestStamps:
    """One request's timeline on its handler thread: ``time.monotonic``
    seconds taken where the work happens, each stage's end being the
    next one's start, so the parts add up to the residence
    (``t_recv``..``t_sent``) with nothing unnamed. ``None`` = never
    reached (a request handed to :meth:`HTTPApp.handle` directly has
    no ``t_recv``/``t_sent``).

    - ``t_recv``: the request line has arrived (``_Handler.handle``)
    - ``t_enter``: headers and body read, ids minted, trace begun;
      routing starts (``pio_http_request_duration_seconds`` starts here)
    - ``t_enq`` / ``t_done`` / ``t_wake``: written by a handler that
      queues its work and blocks for it (the engine server's batcher:
      enqueued, its batch finished, this thread runs again), with the
      timeline of the ``batch`` that served it
    - ``t_return``: :meth:`HTTPApp.handle` returns
    - ``t_sent``: the response is written
    """

    __slots__ = ("t_recv", "t_enter", "t_enq", "t_done", "t_wake",
                 "t_return", "t_sent", "batch", "route", "_span")

    def __init__(self, t_recv: Optional[float] = None):
        self.t_recv = t_recv
        self.t_enter = self.t_enq = self.t_done = self.t_wake = None
        self.t_return = self.t_sent = None
        self.batch: Any = None
        self.route = ""
        self._span: Any = None

    def open_span(self, stage: str) -> None:
        """Open a ``pio:<stage>`` profiler annotation that another
        method of this thread closes (:meth:`close_span`)."""
        self._span = stage_span(stage)
        self._span.__enter__()

    def close_span(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


@dataclass
class Request:
    method: str
    path: str
    query: Dict[str, str]
    headers: Dict[str, str]
    body: bytes
    #: Named groups from the route pattern match.
    path_params: Dict[str, str] = field(default_factory=dict)
    #: Per-request id: echoed from an ``X-Request-ID`` header or minted
    #: here, attached to the access-log line and the response so any
    #: slow query can be decomposed post-hoc.
    request_id: str = ""
    #: Handler-attached observability payload (per-phase timings etc.);
    #: merged into this request's access-log line. Keys starting with
    #: ``_`` are carriers for in-process objects (the live trace) and
    #: never serialize into the log line.
    obs: Dict[str, Any] = field(default_factory=dict)
    #: The live :class:`~predictionio_tpu.obs.trace.Trace` when the app
    #: has a tracer mounted (every request does, cheaply; retention is
    #: the sampled part — docs/tracing.md). Also threaded through
    #: ``obs["_trace"]`` so batcher/pipeline code that only sees the
    #: obs dict can attach stage spans.
    trace: Any = None
    #: This request's stamps (``obs["_stamps"]`` carries the same
    #: object to code that only sees the obs dict).
    stamps: RequestStamps = field(default_factory=RequestStamps)

    def header(self, name: str, default: Optional[str] = None
               ) -> Optional[str]:
        """Case-insensitive header lookup (clients send
        ``traceparent``, ``Traceparent``, ``TraceParent``…)."""
        v = self.headers.get(name)
        if v is not None:
            return v
        lower = name.lower()
        for k, val in self.headers.items():
            if k.lower() == lower:
                return val
        return default

    def json(self) -> Any:
        if not self.body:
            return None
        return json.loads(self.body.decode("utf-8"))

    def form(self) -> Dict[str, str]:
        """Parse an ``application/x-www-form-urlencoded`` body."""
        parsed = parse_qs(self.body.decode("utf-8"), keep_blank_values=True)
        return {k: v[0] for k, v in parsed.items()}


@dataclass
class Response:
    status: int = 200
    body: Any = None
    content_type: str = "application/json"
    headers: Dict[str, str] = field(default_factory=dict)

    def encoded(self) -> bytes:
        if self.body is None:
            return b""
        if isinstance(self.body, bytes):
            return self.body
        if isinstance(self.body, str):
            return self.body.encode("utf-8")
        return json.dumps(self.body).encode("utf-8")


def json_response(body: Any, status: int = 200) -> Response:
    return Response(status=status, body=body)


def make_key_auth(accesskey: Optional[str]) -> Callable[["Request"], None]:
    """Shared ``?accessKey=`` guard (the reference's KeyAuthentication,
    ``common/.../KeyAuthentication.scala:33-58``): no-op when no key is
    configured; constant-time comparison otherwise."""
    import hmac

    def _auth(req: "Request") -> None:
        if accesskey and not hmac.compare_digest(
                req.query.get("accessKey") or "", accesskey):
            raise HTTPError(401, "Invalid accessKey.")

    return _auth


class SessionAuth:
    """Cookie-session guard for browser-facing servers (dashboard).

    Accepts the accessKey once — via ``?accessKey=`` or an
    ``Authorization: Bearer`` header — then mints an HttpOnly session
    cookie, so generated links never embed the secret (which would leak
    into browser history, proxy logs, and Referer headers). The reference
    dashboard had no auth at all; this extends its KeyAuthentication
    pattern (``common/.../KeyAuthentication.scala:33-58``) to browsers.

    Calling the instance authorizes a request and returns a ``Set-Cookie``
    header value when a new session was minted (else ``None``); raises
    :class:`HTTPError` 401 on failure.
    """

    MAX_SESSIONS = 4096

    def __init__(self, accesskey: Optional[str],
                 cookie_name: str = "pio_dashboard_session",
                 secure: bool = False):
        import hmac as _hmac
        self._hmac = _hmac
        self.accesskey = accesskey
        self.cookie_name = cookie_name
        self.secure = secure
        #: insertion-ordered so overflow evicts the oldest session only —
        #: a cookie-less poller (curl health check) must not wholesale
        #: log out live browser sessions; values are monotonic expiry times
        self._tokens: "Dict[str, float]" = {}
        self._lock = new_lock("SessionKeyAuth._lock")

    #: sessions expire after 24h; a captured cookie does not authenticate
    #: for the life of the server process
    TTL_SECONDS = 24 * 3600.0

    def _cookie_token(self, req: "Request") -> Optional[str]:
        header = req.headers.get("Cookie") or ""
        for part in header.split(";"):
            name, _, value = part.strip().partition("=")
            if name == self.cookie_name and value:
                return value
        return None

    def __call__(self, req: "Request") -> Optional[str]:
        if not self.accesskey:
            return None
        import time as _time
        now = _time.monotonic()
        tok = self._cookie_token(req)
        if tok is not None:
            with self._lock:
                for t, expiry in self._tokens.items():
                    if self._hmac.compare_digest(tok, t):
                        if now <= expiry:
                            return None
                        break  # expired: fall through to key auth
        supplied = req.query.get("accessKey") or ""
        if not supplied:
            auth = req.headers.get("Authorization") or ""
            if auth.startswith("Bearer "):
                supplied = auth[len("Bearer "):]
        if supplied and self._hmac.compare_digest(supplied, self.accesskey):
            import secrets
            tok = secrets.token_urlsafe(32)
            with self._lock:
                expired = [t for t, exp in self._tokens.items()
                           if now > exp]
                for t in expired:
                    del self._tokens[t]
                while len(self._tokens) >= self.MAX_SESSIONS:
                    self._tokens.pop(next(iter(self._tokens)))
                self._tokens[tok] = now + self.TTL_SECONDS
            attrs = "; HttpOnly; SameSite=Strict; Path=/"
            if self.secure:
                attrs += "; Secure"
            return f"{self.cookie_name}={tok}{attrs}"
        raise HTTPError(401, "Invalid accessKey.")


def ssl_context_from(cert_path: Optional[str] = None,
                     key_path: Optional[str] = None):
    """Build a server SSLContext from PEM files; falls back to the
    ``PIO_SSL_CERT``/``PIO_SSL_KEY`` env vars; None when unconfigured
    (the reference's keystore-driven SSLConfiguration, PEM-based)."""
    import os
    import ssl

    cert = cert_path or os.environ.get("PIO_SSL_CERT")
    key = key_path or os.environ.get("PIO_SSL_KEY")
    if not cert:
        if key:
            raise ValueError("SSL key configured without a certificate; "
                             "set both or neither")
        return None
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(cert, key or None)
    return ctx


Handler = Callable[[Request], Response]


class HTTPApp:
    """Routes ``(method, path-regex) → handler``; first match wins.

    When a :class:`~predictionio_tpu.obs.MetricsRegistry` is mounted
    (:func:`mount_metrics`), every request is timed into a per-route
    latency histogram, counted by status, stamped with a request id, and
    logged as one structured JSON access-log line.
    """

    def __init__(self, name: str = "app"):
        self.name = name
        self._routes: List[Tuple[str, re.Pattern, str, Handler]] = []
        self.metrics = None  # set by mount_metrics
        self._http_hist = None
        self._http_count = None
        self._io_read = self._io_write = self._http_residence = None
        #: bound children by label values: ``labels()`` validates and
        #: sorts its keywords under a lock on every call
        self._children: Dict[Tuple, Any] = {}
        #: called with the request once its response is written and
        #: every stamp is taken (the engine server observes its
        #: ``respond`` phase here)
        self.on_sent: Optional[Callable[[Request], None]] = None
        self.tracer = None  # set by mount_metrics (obs.trace.Tracer)
        #: probabilistic sampling of the structured access log
        #: (ISSUE 12 satellite): at high qps the per-request
        #: ``json.dumps`` is real money — sample the successes, but
        #: errors and 503s ALWAYS log (they are why the log exists)
        self.access_log_sample = 1.0
        #: request ids: a handle for logs and traces, not a secret, so
        #: a generator seeded once and no system call a request
        self._id_bits = random.Random(os.urandom(16)).getrandbits

    def route(self, method: str, pattern: str) -> Callable[[Handler], Handler]:
        compiled = re.compile(f"^{pattern}$")

        def deco(fn: Handler) -> Handler:
            self._routes.append((method.upper(), compiled, pattern, fn))
            return fn
        return deco

    def enable_metrics(self, registry) -> None:
        """Record per-route request latency/status into ``registry``."""
        self.metrics = registry
        self._http_hist = registry.histogram(
            "pio_http_request_duration_seconds",
            "HTTP request wall time by route")
        self._http_count = registry.counter(
            "pio_http_requests_total",
            "HTTP requests by route, method, and status code")
        http_io = registry.histogram(
            "pio_http_io_seconds",
            "Handler-thread time outside the app, per request: "
            "part=read (request line arrived -> routing starts: "
            "headers, body, ids, trace begin), part=write (handle "
            "returned -> response written)")
        self._http_residence = registry.histogram(
            "pio_http_residence_seconds",
            "Request line arrived -> response written, by route: all "
            "the server does for a request")
        self._io_read = http_io.labels(part="read")
        self._io_write = http_io.labels(part="write")

    def _child(self, family, **labels: str):
        key = (family.name, *labels.values())
        child = self._children.get(key)
        if child is None:
            child = self._children[key] = family.labels(**labels)
        return child

    def _dispatch(self, req: Request) -> Tuple[Response, str]:
        """Route + run the handler; returns (response, route pattern —
        the bounded-cardinality label, never the raw path)."""
        path_matched = False
        for method, pattern, raw, fn in self._routes:
            m = pattern.match(req.path)
            if m:
                path_matched = True
                if method == req.method:
                    req.path_params = m.groupdict()
                    try:
                        return fn(req), raw
                    except HTTPError as e:
                        return (json_response({"message": e.message},
                                              e.status), raw)
                    except (StorageError, FaultError) as e:
                        # an unavailable backing store is a RETRYABLE
                        # dependency outage, not a server bug: 503 with
                        # Retry-After (and a clean message — never a
                        # traceback body) instead of a bare 500, so
                        # well-behaved clients back off and retry
                        # (ISSUE 11 satellite)
                        resp = json_response(
                            {"message": "backing store unavailable: "
                                        f"{e}"}, 503)
                        resp.headers["Retry-After"] = str(
                            RETRY_AFTER_SECONDS)
                        return resp, raw
                    except Exception as e:  # noqa: BLE001 — server boundary
                        return json_response({"message": str(e)}, 500), raw
        if path_matched:
            return json_response({"message": "Method Not Allowed"},
                                 405), "(method-not-allowed)"
        return json_response({"message": "Not Found"}, 404), "(unmatched)"

    def handle(self, req: Request) -> Response:
        req.request_id = (req.headers.get("X-Request-ID")
                          or "%016x" % self._id_bits(64))
        tracer = self.tracer
        if tracer is not None:
            # W3C context propagation (ISSUE 12): continue the caller's
            # trace when a valid ``traceparent`` rides in, else mint a
            # fresh one — tied to X-Request-ID either way
            req.trace = tracer.begin(
                f"{req.method} {req.path}",
                traceparent=req.header("traceparent"),
                request_id=req.request_id, server=self.name)
            req.obs["_trace"] = req.trace
        st = req.stamps
        req.obs["_stamps"] = st
        t0 = st.t_enter = time.monotonic()
        st.close_span()  # pio:http_read, where the server opened one
        resp, route = self._dispatch(req)
        dt = time.monotonic() - t0
        st.route = route
        resp.headers.setdefault("X-Request-ID", req.request_id)
        if self.metrics is not None:
            hist = self._child(self._http_hist, route=route)
            hist.observe(dt)
            self._child(self._http_count, route=route, method=req.method,
                        status=str(resp.status)).inc()
            if req.trace is not None:
                req.trace.exemplar(hist, dt)
        if req.trace is not None:
            req.trace.set_attr("route", route)
            resp.headers.setdefault("traceparent",
                                    req.trace.traceparent())
            retained, reason = tracer.finish(req.trace,
                                             status=resp.status,
                                             duration=dt)
            if retained:
                resp.headers.setdefault("X-Trace-Retained", reason)
        if access_log.isEnabledFor(logging.INFO) \
                and self._log_this(resp.status):
            line = {"server": self.name, "requestId": req.request_id,
                    "method": req.method, "path": req.path,
                    "status": resp.status,
                    "durationMs": round(dt * 1000, 3)}
            if req.trace is not None:
                line["traceId"] = req.trace.trace_id
            line.update((k, v) for k, v in req.obs.items()
                        if not k.startswith("_"))
            access_log.info(json.dumps(line))
        st.t_return = time.monotonic()
        return resp

    def sent(self, req: Request) -> None:
        """The server wrote ``req``'s response: take the last stamp and
        observe the request's read, write and residence (after the
        write, so off the client's path)."""
        st = req.stamps
        st.t_sent = time.monotonic()
        if self.metrics is not None and st.t_recv is not None \
                and st.t_return is not None:
            self._io_read.observe(st.t_enter - st.t_recv)
            self._io_write.observe(st.t_sent - st.t_return)
            self._child(self._http_residence, route=st.route).observe(
                st.t_sent - st.t_recv)
        if self.on_sent is not None:
            self.on_sent(req)

    def refused(self, status: int) -> None:
        """The server's request loop answered a request itself (it
        never parsed into a :class:`Request`): counted, under a route
        label of its own and no method (a client's token is unbounded)."""
        if self.metrics is not None:
            self._child(self._http_count, route=LOOP_ROUTE, method="-",
                        status=str(status)).inc()

    def _log_this(self, status: int) -> bool:
        """Access-log admission: errors/503s always; successes at the
        configured sample rate (``ServerConfig.access_log_sample``)."""
        if status >= 400:
            return True
        sample = self.access_log_sample
        if sample >= 1.0:
            return True
        if sample <= 0.0:
            return False
        return random.random() < sample


class HTTPError(Exception):
    """Raise inside a handler to produce a JSON error response."""

    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status
        self.message = message


#: content type of the OpenMetrics exposition (the format that can
#: carry exemplars); negotiated via the Accept header on /metrics
OPENMETRICS_CONTENT_TYPE = \
    "application/openmetrics-text; version=1.0.0; charset=utf-8"


def mount_metrics(app: HTTPApp, registry, server_name: Optional[str] = None,
                  status: Optional[Callable[[], Dict[str, Any]]] = None,
                  runtime: bool = True, tracer=None) -> None:
    """The shared telemetry mount every server goes through:

    - instruments the app's request path (latency histogram, status
      counters, request ids, access log) via :meth:`HTTPApp.enable_metrics`
    - registers the standard runtime series (build info, XLA compiles,
      transfer-guard violations, per-device HBM)
    - adds ``GET /metrics`` — Prometheus text format 0.0.4, or
      OpenMetrics 1.0 (with bucket exemplars) when the scraper sends
      ``Accept: application/openmetrics-text``
    - when ``status`` is given, adds ``GET /status.json`` returning its
      dict enriched with the registry snapshot (servers with a bespoke
      status route — the engine server — pass ``status=None`` and
      enrich their own)
    - mounts a request :class:`~predictionio_tpu.obs.trace.Tracer` +
      ``GET /trace.json`` (the flight-recorder read side,
      docs/tracing.md). ``tracer=None`` builds a default one;
      ``tracer=False`` disables tracing for this app.
    """
    from ..obs import Tracer, register_runtime_metrics

    if runtime:
        register_runtime_metrics(registry, server_name or app.name)
    app.enable_metrics(registry)
    if tracer is None:
        tracer = Tracer()
    if tracer is not False:
        app.tracer = tracer
        tracer.register_metrics(registry)
        mount_trace_routes(app, tracer)

    # scrape self-cost guard (ISSUE 17 satellite): rendering the
    # exposition is work the server pays PER SCRAPER — an aggregator
    # polling N replicas every 250ms must be able to see (and a
    # regression test bound) what that costs. Sub-ms bounds: a healthy
    # render of a few hundred series is tens of microseconds.
    render_hist = registry.histogram(
        "pio_metrics_render_seconds",
        "Wall time to render one /metrics(.json) exposition, by format",
        bounds=[0.0001 * (2.0 ** i) for i in range(16)])

    @app.route("GET", "/metrics")
    def metrics(req: Request) -> Response:
        # content negotiation (ISSUE 12 satellite): OpenMetrics is
        # required for exemplar rendering; everything else gets the
        # 0.0.4 text format it always got
        accept = req.header("Accept") or ""
        openmetrics = "application/openmetrics-text" in accept
        t0 = time.perf_counter()
        body = registry.render(openmetrics=openmetrics)
        render_hist.labels(
            format="openmetrics" if openmetrics else "text"
        ).observe(time.perf_counter() - t0)
        if openmetrics:
            return Response(body=body,
                            content_type=OPENMETRICS_CONTENT_TYPE)
        return Response(
            body=body,
            content_type="text/plain; version=0.0.4; charset=utf-8")

    @app.route("GET", "/metrics.json")
    def metrics_json(req: Request) -> Response:
        # the fleet-scrape lane (ISSUE 17): full-fidelity JSON with
        # raw cumulative histogram buckets, so the aggregator merges
        # pooled populations instead of averaging percentiles
        t0 = time.perf_counter()
        resp = json_response(registry.export())
        render_hist.labels(format="json").observe(
            time.perf_counter() - t0)
        return resp

    if status is not None:
        @app.route("GET", "/status.json")
        def status_json(req: Request) -> Response:
            return json_response(dict(status(),
                                      metrics=registry.snapshot()))


def mount_trace_routes(app: HTTPApp, tracer) -> None:
    """``GET /trace.json`` — the flight recorder's read side:

    - ``?id=<trace id>`` → that retained trace as Chrome/Perfetto
      trace-event JSON (load it at ui.perfetto.dev)
    - ``?slowest=N`` → summaries of the N slowest retained traces
    - no params → recorder status (counts by reason, ring occupancy,
      live slow threshold, recent retentions)
    """

    @app.route("GET", "/trace.json")
    def trace_json(req: Request) -> Response:
        trace_id = req.query.get("id")
        if trace_id:
            trace = tracer.recorder.get(trace_id)
            if trace is None:
                raise HTTPError(
                    404, f"trace {trace_id!r} is not retained (it was "
                         f"fast and healthy, or has aged out of the "
                         f"ring)")
            return json_response(trace.to_trace_events())
        if "slowest" in req.query:
            try:
                n = int(req.query["slowest"])
            except ValueError:
                raise HTTPError(400, "slowest must be an integer")
            return json_response({
                "traces": [t.summary()
                           for t in tracer.recorder.slowest(n)]})
        return json_response(tracer.status())


#: route label of the answers the request loop gives itself (400, 414,
#: 431, 501, 505) in ``pio_http_requests_total``
LOOP_ROUTE = "(http-loop)"

#: the methods a route table can hold; any other is answered 501
_METHODS = frozenset(("GET", "POST", "PUT", "DELETE"))
#: the standard library's limits, kept: bytes of a request line or of
#: one header line, and header lines of one request
_MAX_LINE = 65536
_MAX_HEADERS = 100
_REASONS = {s.value: s.phrase for s in HTTPStatus}
_SERVER = "PredictionIO-TPU Python/" + sys.version.split()[0]
_DAYS = ("Mon", "Tue", "Wed", "Thu", "Fri", "Sat", "Sun")
_MONTHS = ("Jan", "Feb", "Mar", "Apr", "May", "Jun",
           "Jul", "Aug", "Sep", "Oct", "Nov", "Dec")


def _is_http11(version: str) -> bool:
    """Whether a request's ``HTTP/x.y`` is 1.1 or a later 1.y (its
    connection stays open unless it says otherwise) and not 1.0;
    :class:`HTTPError` 400 for a token that is no version, 505 for a
    major version other than 1."""
    if version == "HTTP/1.1":
        return True
    if version == "HTTP/1.0":
        return False
    major, dot, minor = version[5:].partition(".")
    try:
        if not version.startswith("HTTP/") or not dot:
            raise ValueError(version)
        major, minor = int(major), int(minor)
    except ValueError:
        raise HTTPError(400, f"Bad request version ({version!r})")
    if major != 1:
        raise HTTPError(505, f"Invalid HTTP version ({version[5:]})")
    return minor >= 1


class _Handler(StreamRequestHandler):
    """One connection's thread: reads requests off the socket, hands
    each to the app and writes its response, until either side closes.
    HTTP/1.0 and 1.1 as the servers' clients speak them
    (docs/observability.md, "The HTTP the servers speak"); the framing
    is split by hand and a response leaves in one ``sendall``, because
    in a full interpreter every system call is a turn in the queue for
    the interpreter lock (PERF.md finding 42.1)."""

    app: HTTPApp  # bound by AppServer
    # a response is one small write: with Nagle on, it would wait for
    # the peer's delayed ACK of the one before (~40 ms a keep-alive
    # request, measured)
    disable_nagle_algorithm = True

    def setup(self) -> None:
        # once a connection, on the thread ThreadingHTTPServer started
        # for it (``Thread-N (process_request_thread)`` until here)
        name_os_thread("http-handler")
        super().setup()
        self._date_at = 0
        self._date = ""

    def handle(self) -> None:
        try:
            try:
                self._serve()
            except HTTPError as e:  # _read_request's: answered and counted
                self.request.sendall(self._wire(
                    json_response({"message": e.message}, e.status),
                    "close"))
                self.app.refused(e.status)
        except OSError:
            pass  # the peer went away mid-request: nobody to answer

    def _serve(self) -> None:
        app, rfile, sock = self.app, self.rfile, self.request
        while True:
            line = rfile.readline(_MAX_LINE + 1)
            if not line:
                return  # the peer closed between requests
            # the request line has arrived: the request's first stamp
            st = RequestStamps(time.monotonic())
            st.open_span("http_read")  # closed where routing starts
            try:
                req, connection = self._read_request(line, st)
                if req is None:
                    return
                resp = app.handle(req)
            finally:
                st.close_span()  # a read that failed never got there
            with stage_span("http_write"):
                sock.sendall(self._wire(resp, connection))
            app.sent(req)
            if connection == "close":
                return

    def _read_request(self, line: bytes, st: RequestStamps
                      ) -> Tuple[Optional[Request], str]:
        """The request whose first line is ``line``, and its response's
        ``Connection``: ``close`` (the loop ends with it), ``keep-alive``
        where the client asked for that, nothing where HTTP/1.1's
        default holds. No request where the peer closed before it was
        whole; :class:`HTTPError` where the loop answers it itself."""
        if len(line) > _MAX_LINE:
            raise HTTPError(414, "Request-URI Too Long")
        if not line.endswith(b"\n"):
            return None, "close"  # the stream ended inside the line
        words = line.decode("iso-8859-1").split()
        if len(words) != 3:
            raise HTTPError(400, f"Bad request syntax ({line[:80]!r})")
        method, target, version = words
        http11 = _is_http11(version)
        connection = "" if http11 else "close"
        headers: Dict[str, str] = {}
        length, expect = 0, False
        rfile = self.rfile
        for _ in range(_MAX_HEADERS + 1):
            line = rfile.readline(_MAX_LINE + 1)
            if line in (b"\r\n", b"\n"):
                break
            if len(line) > _MAX_LINE:
                raise HTTPError(431, "Header line too long")
            if not line.endswith(b"\n"):
                return None, "close"
            name, colon, value = line.decode("iso-8859-1").partition(":")
            if not colon or not name or name != name.strip():
                # no name, a folded line (obsolete since RFC 7230) or
                # space around the name: refused, never guessed at
                raise HTTPError(400, f"Bad header line ({line[:80]!r})")
            headers[name] = value = value.strip()
            name = name.lower()
            if name == "content-length":
                try:
                    length = int(value)
                except ValueError:
                    length = -1
                if length < 0:
                    raise HTTPError(400, f"Bad Content-Length ({value!r})")
            elif name == "connection":
                value = value.lower()
                if value in ("close", "keep-alive"):
                    connection = value
            elif name == "expect":
                expect = value.lower() == "100-continue"
            elif name == "transfer-encoding":
                # a body is read by Content-Length alone; a chunked
                # one would be taken for the next request
                raise HTTPError(501, f"Unsupported Transfer-Encoding "
                                     f"({value!r})")
        else:
            raise HTTPError(431, f"More than {_MAX_HEADERS} headers")
        if method not in _METHODS:
            raise HTTPError(501, f"Unsupported method ({method!r})")
        if expect and http11:
            # curl sends it with any body over 1,024 bytes and waits
            self.request.sendall(b"HTTP/1.1 100 Continue\r\n\r\n")
        body = rfile.read(length) if length else b""
        if len(body) < length:
            return None, "close"
        if "?" in target:
            parsed = urlparse(target)
            target = parsed.path
            query = {k: v[0] for k, v in parse_qs(parsed.query).items()}
        else:
            query = {}
        return Request(method=method, path=target, query=query,
                       headers=headers, body=body, stamps=st), connection

    def _wire(self, resp: Response, connection: str) -> bytes:
        """A whole response as the bytes of one write."""
        payload = resp.encoded()
        now = int(time.time())
        if now != self._date_at:  # formatted once a second it is true of
            y, mo, d, h, mi, sec, wd = time.gmtime(now)[:7]
            self._date = "%s, %02d %s %04d %02d:%02d:%02d GMT" % (
                _DAYS[wd], d, _MONTHS[mo - 1], y, h, mi, sec)
            self._date_at = now
        head = [f"HTTP/1.1 {resp.status} {_REASONS.get(resp.status, '')}\r\n"
                f"Server: {_SERVER}\r\nDate: {self._date}\r\n"
                f"Content-Type: {resp.content_type}\r\n"
                f"Content-Length: {len(payload)}\r\n"]
        head += [f"{k}: {v}\r\n" for k, v in resp.headers.items()]
        if connection:
            head.append(f"Connection: {connection}\r\n")
        head.append("\r\n")
        return "".join(head).encode("latin-1") + payload


class _AppHTTPServer(ThreadingHTTPServer):
    # listen backlog: the stdlib default (5) resets connections the
    # moment a burst of concurrent clients lands — the serving
    # micro-batcher exists precisely to absorb such bursts
    request_queue_size = 256


class AppServer:
    """Owns a ``ThreadingHTTPServer`` for one :class:`HTTPApp`; start in a
    daemon thread (tests, embedded) or serve on the main thread (CLI)."""

    def __init__(self, app: HTTPApp, host: str = "0.0.0.0", port: int = 0,
                 ssl_context=None):
        handler = type("BoundHandler", (_Handler,), {"app": app})
        self.httpd = _AppHTTPServer((host, port), handler)
        if ssl_context is not None:
            # HTTPS (the reference's JKS SSLConfiguration,
            # common/.../SSLConfiguration.scala:26-58, PEM-based here)
            self.httpd.socket = ssl_context.wrap_socket(
                self.httpd.socket, server_side=True)
        self.app = app
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start_background(self) -> "AppServer":
        self._thread = RoleThread(
            target=self.httpd.serve_forever,
            name=f"http-acceptor-{self.app.name}", daemon=True)
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self.httpd.serve_forever()

    def shutdown(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
