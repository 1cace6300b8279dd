"""The HTTP a handler thread speaks, byte for byte over a raw socket
against a bare :class:`HTTPApp`: every server of the framework is one
of these, so none is started here. ``_Handler`` splits the framing by
hand (no ``http.server`` request handler, no ``email`` parser), and
these cases are what holds it to the protocol its clients use."""

from __future__ import annotations

import email.utils
import http.client
import json
import os
import re
import shutil
import socket
import subprocess
import time
import urllib.request

import pytest

from predictionio_tpu.obs import MetricsRegistry
from predictionio_tpu.server.http import (
    LOOP_ROUTE,
    AppServer,
    HTTPApp,
    Request,
    json_response,
    ssl_context_from,
)


def _app() -> HTTPApp:
    app = HTTPApp("wire")

    @app.route("POST", "/echo")
    @app.route("PUT", "/echo")
    @app.route("GET", "/echo")
    @app.route("DELETE", "/echo")
    def echo(req: Request):
        return json_response({
            "method": req.method, "path": req.path, "query": req.query,
            "headers": req.headers, "n": len(req.body),
            "body": req.body.decode("latin-1"), "id": req.request_id})

    app.enable_metrics(MetricsRegistry())
    return app


@pytest.fixture(scope="module")
def server():
    srv = AppServer(_app(), host="127.0.0.1", port=0).start_background()
    yield srv
    srv.shutdown()


def _connect(server) -> socket.socket:
    return socket.create_connection(("127.0.0.1", server.port), timeout=10)


def _read_response(f):
    """One response off a socket's file: (status line, headers as a
    list of pairs, body by ``Content-Length``); ``None`` at the end of
    the stream."""
    status = f.readline()
    if not status:
        return None
    headers = []
    while True:
        line = f.readline()
        if line in (b"\r\n", b""):
            break
        name, _, value = line.decode("latin-1").partition(":")
        headers.append((name, value.strip()))
    length = int(dict(headers).get("Content-Length", 0))
    return status.decode("latin-1").rstrip("\r\n"), headers, f.read(length)


def _post(body: bytes, *extra: str, target: str = "/echo",
          version: str = "HTTP/1.1") -> bytes:
    lines = [f"POST {target} {version}", "Host: t",
             f"Content-Length: {len(body)}", *extra]
    return ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1") + body


def _loop_count(server, status: int) -> float:
    fam = server.app.metrics.export().get("pio_http_requests_total") or {}
    return sum(c["value"] for c in fam.get("children", ())
               if c["labels"] == {"route": LOOP_ROUTE, "method": "-",
                                  "status": str(status)})


def test_two_hundred_requests_on_one_connection(server):
    with _connect(server) as s, s.makefile("rb") as f:
        for i in range(200):
            s.sendall(_post(b"%d" % i))
            status, _, body = _read_response(f)
            assert status == "HTTP/1.1 200 OK"
            assert json.loads(body)["body"] == str(i)


def test_two_requests_in_one_send(server):
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(_post(b"first") + _post(b"second"))
        assert json.loads(_read_response(f)[2])["body"] == "first"
        assert json.loads(_read_response(f)[2])["body"] == "second"


@pytest.mark.parametrize("version, connection, stays, says", [
    ("HTTP/1.1", None, True, None),
    ("HTTP/1.1", "close", False, "close"),
    ("HTTP/1.1", "Close", False, "close"),
    ("HTTP/1.0", None, False, "close"),
    ("HTTP/1.0", "keep-alive", True, "keep-alive"),
    ("HTTP/1.0", "Keep-Alive", True, "keep-alive"),
])
def test_who_closes_the_connection(server, version, connection, stays, says):
    extra = [] if connection is None else [f"Connection: {connection}"]
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(_post(b"a", *extra, version=version))
        status, headers, body = _read_response(f)
        assert status == "HTTP/1.1 200 OK"
        assert dict(headers).get("Connection") == says
        s.sendall(_post(b"b", *extra, version=version))
        again = _read_response(f)
        if stays:
            assert json.loads(again[2])["body"] == "b"
        else:
            assert again is None


def test_expect_100_continue(server):
    """curl sends it with any body over 1,024 bytes and waits for the
    interim response before the body (a generative query's history)."""
    body = b"x" * 2048
    head = _post(body, "Expect: 100-continue")[:-len(body)]
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(head)
        assert f.readline() == b"HTTP/1.1 100 Continue\r\n"
        assert f.readline() == b"\r\n"
        s.sendall(body)
        status, _, answer = _read_response(f)
        assert status == "HTTP/1.1 200 OK"
        assert json.loads(answer)["n"] == 2048


def test_an_http10_client_is_sent_no_100_continue(server):
    body = b"x" * 10
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(_post(body, "Expect: 100-continue", version="HTTP/1.0"))
        assert _read_response(f)[0] == "HTTP/1.1 200 OK"


def test_a_32k_body_in_three_pieces(server):
    body = os.urandom(16384).hex().encode()
    wire = _post(body)
    cuts = [0, 40, len(wire) - 20000, len(wire)]
    with _connect(server) as s, s.makefile("rb") as f:
        for a, b in zip(cuts, cuts[1:]):
            s.sendall(wire[a:b])
            time.sleep(0.05)
        answer = json.loads(_read_response(f)[2])
        assert answer["n"] == 32768 and answer["body"] == body.decode()


def test_header_names_are_kept_as_sent_and_matched_in_any_case(server):
    wire = (b"POST /echo HTTP/1.1\r\nhost: t\r\ncontent-length: 3\r\n"
            b"connection: close\r\nx-custom:  padded \r\n\r\nabc")
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(wire)
        _, headers, body = _read_response(f)
        answer = json.loads(body)
        assert answer["body"] == "abc"
        assert answer["headers"] == {
            "host": "t", "content-length": "3", "connection": "close",
            "x-custom": "padded"}
        assert dict(headers)["Connection"] == "close"
        assert _read_response(f) is None


def test_a_repeated_header_keeps_its_last_value(server):
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(_post(b"", "X-Twice: one", "X-Twice: two"))
        assert json.loads(_read_response(f)[2])["headers"]["X-Twice"] == "two"


@pytest.mark.parametrize("target, path, query", [
    ("/echo", "/echo", {}),
    ("/echo?accessKey=k&x=1&x=2", "/echo", {"accessKey": "k", "x": "1"}),
    ("/echo?q=a%20b+c#frag", "/echo", {"q": "a b c"}),
    ("/echo?", "/echo", {}),
])
def test_a_target_with_a_query_string_and_one_without(server, target, path,
                                                      query):
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(_post(b"", target=target))
        answer = json.loads(_read_response(f)[2])
        assert (answer["path"], answer["query"]) == (path, query)


@pytest.mark.parametrize("method", ["GET", "POST", "PUT", "DELETE"])
def test_the_four_methods_reach_the_routes(server, method):
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(f"{method} /echo HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        assert json.loads(_read_response(f)[2])["method"] == method


def test_routing_misses_are_the_apps_not_the_loops(server):
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(b"GET /nowhere HTTP/1.1\r\nHost: t\r\n\r\n"
                  b"PUT /nowhere HTTP/1.1\r\nHost: t\r\n\r\n")
        assert _read_response(f)[0] == "HTTP/1.1 404 Not Found"
        assert _read_response(f)[0] == "HTTP/1.1 404 Not Found"


_LONG = "a" * 65536
_REFUSED = {
    "no-version": (b"GET /echo\r\n\r\n", 400),
    "one-word": (b"GARBAGE\r\n\r\n", 400),
    "blank-line": (b"\r\n", 400),
    "four-words": (b"GET /echo extra HTTP/1.1\r\n\r\n", 400),
    "version-not-http": (b"GET /echo FTP/1.1\r\n\r\n", 400),
    "version-no-minor": (b"GET /echo HTTP/1\r\n\r\n", 400),
    "version-not-a-number": (b"GET /echo HTTP/one.one\r\n\r\n", 400),
    "header-without-colon": (b"GET /echo HTTP/1.1\r\nHost t\r\n\r\n", 400),
    "header-without-name": (b"GET /echo HTTP/1.1\r\n: t\r\n\r\n", 400),
    "space-before-colon": (b"GET /echo HTTP/1.1\r\nHost : t\r\n\r\n", 400),
    "folded-header": (b"GET /echo HTTP/1.1\r\nX-A: one\r\n two\r\n\r\n", 400),
    "content-length-not-a-number":
        (b"POST /echo HTTP/1.1\r\nContent-Length: ten\r\n\r\n", 400),
    "content-length-negative":
        (b"POST /echo HTTP/1.1\r\nContent-Length: -1\r\n\r\n", 400),
    "line-too-long":
        (f"GET /{_LONG} HTTP/1.1\r\nHost: t\r\n\r\n".encode(), 414),
    "header-too-long":
        (f"GET /echo HTTP/1.1\r\nX-Long: {_LONG}\r\n\r\n".encode(), 431),
    "101-headers":
        (("GET /echo HTTP/1.1\r\n" + "".join(
            f"X-{i}: {i}\r\n" for i in range(101)) + "\r\n").encode(), 431),
    "PATCH": (b"PATCH /echo HTTP/1.1\r\nHost: t\r\n\r\n", 501),
    "HEAD": (b"HEAD /echo HTTP/1.1\r\nHost: t\r\n\r\n", 501),
    "OPTIONS": (b"OPTIONS * HTTP/1.1\r\nHost: t\r\n\r\n", 501),
    "lower-case-method": (b"get /echo HTTP/1.1\r\nHost: t\r\n\r\n", 501),
    "chunked-body": (b"POST /echo HTTP/1.1\r\nTransfer-Encoding: chunked"
                     b"\r\n\r\n3\r\nabc\r\n0\r\n\r\n", 501),
    "HTTP/2.0": (b"GET /echo HTTP/2.0\r\nHost: t\r\n\r\n", 505),
    "HTTP/0.9": (b"GET /echo HTTP/0.9\r\nHost: t\r\n\r\n", 505),
}


@pytest.mark.parametrize("case", sorted(_REFUSED))
def test_what_the_loop_answers_itself(server, case):
    """Answered by the request loop, in JSON like the app's own errors,
    with ``Connection: close`` and a closed connection, and counted in
    ``pio_http_requests_total`` under the loop's own route label."""
    wire, want = _REFUSED[case]
    before = _loop_count(server, want)
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(wire)
        status, headers, body = _read_response(f)
        assert int(status.split()[1]) == want
        assert status.startswith("HTTP/1.1 ")
        assert dict(headers)["Connection"] == "close"
        assert dict(headers)["Content-Type"] == "application/json"
        assert json.loads(body)["message"]
        assert _read_response(f) is None
    deadline = time.monotonic() + 5
    while _loop_count(server, want) != before + 1 \
            and time.monotonic() < deadline:
        time.sleep(0.01)  # counted after the write
    assert _loop_count(server, want) == before + 1


def test_a_hundred_headers_and_a_line_at_the_limit_are_served(server):
    target = "/echo?" + "a" * (65536 - len("GET /echo? HTTP/1.1\r\n"))
    wire = (f"GET {target} HTTP/1.1\r\n" + "".join(
        f"X-{i}: {i}\r\n" for i in range(100)) + "\r\n").encode()
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(wire)
        status, _, body = _read_response(f)
        assert status == "HTTP/1.1 200 OK"
        assert len(json.loads(body)["headers"]) == 100


@pytest.mark.parametrize("sent", [
    b"POST /echo HTTP/1.1\r\nHost: t\r\nContent-Le",
    b"POST /echo HTTP/1.1\r\nHost: t\r\n",
    b"POST /echo HTTP/1.1\r\nHost: t\r\nContent-Length: 100\r\n\r\nhalf",
    b"POST /echo HTTP/1.1",
], ids=["mid-header-line", "mid-headers", "mid-body", "mid-request-line"])
def test_a_peer_that_goes_away_leaves_the_server_serving(server, sent,
                                                         capfd):
    refused = {s: _loop_count(server, s) for s in (400, 414, 431, 501, 505)}
    with _connect(server) as s:
        s.sendall(sent)
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(_post(b"still here"))
        assert json.loads(_read_response(f)[2])["body"] == "still here"
    time.sleep(0.1)  # the abandoned connection's thread has ended
    assert "Traceback" not in capfd.readouterr().err  # a quiet exit
    assert refused == {s: _loop_count(server, s) for s in refused}


def test_a_reset_while_the_response_is_written_is_quiet(server, capfd):
    import struct
    with _connect(server) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))  # close() sends a reset
        s.sendall(_post(b"x") * 50)
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(_post(b"still here"))
        assert json.loads(_read_response(f)[2])["body"] == "still here"
    time.sleep(0.1)
    assert "Traceback" not in capfd.readouterr().err


def test_the_headers_every_response_carries(server):
    with _connect(server) as s, s.makefile("rb") as f:
        s.sendall(_post(b"{}"))
        _, headers, body = _read_response(f)
    names = [k for k, _ in headers]
    assert names[:4] == ["Server", "Date", "Content-Type", "Content-Length"]
    assert len(set(names)) == len(names)
    h = dict(headers)
    assert h["Server"].startswith("PredictionIO-TPU")
    sent = email.utils.parsedate_to_datetime(h["Date"])
    assert h["Date"].endswith(" GMT") and abs(
        sent.timestamp() - time.time()) < 5
    assert h["Date"] == email.utils.formatdate(sent.timestamp(), usegmt=True)
    assert h["Content-Type"] == "application/json"
    assert int(h["Content-Length"]) == len(body)
    assert h["X-Request-ID"] == json.loads(body)["id"]


def test_round_trips_through_http_client(server):
    """What ``cellbench/loadgen.py`` drives the cells with."""
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=10)
    try:
        for i in range(20):
            conn.request("POST", "/echo?i=%d" % i,
                         body=json.dumps({"user": i}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            answer = json.loads(resp.read())
            assert resp.status == 200 and not resp.will_close
            assert answer["query"] == {"i": str(i)}
            assert json.loads(answer["body"]) == {"user": i}
            assert answer["headers"]["Content-Type"] == "application/json"
    finally:
        conn.close()


def test_round_trips_through_urllib(server):
    url = f"http://127.0.0.1:{server.port}/echo?accessKey=k"
    req = urllib.request.Request(url, data=b'{"a": 1}', method="POST")
    with urllib.request.urlopen(req, timeout=10) as resp:
        answer = json.loads(resp.read())
        assert resp.status == 200
        assert resp.headers["Connection"] == "close"  # urllib asks for it
    assert answer["query"] == {"accessKey": "k"} and answer["n"] == 8
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/nowhere", timeout=10)
    assert e.value.code == 404
    assert json.loads(e.value.read()) == {"message": "Not Found"}


@pytest.mark.skipif(shutil.which("openssl") is None,
                    reason="no openssl to make a certificate with")
def test_round_trips_through_tls(tmp_path):
    import ssl
    cert, key = tmp_path / "cert.pem", tmp_path / "key.pem"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-keyout",
         str(key), "-out", str(cert), "-days", "1", "-nodes", "-subj",
         "/CN=localhost"], check=True, capture_output=True)
    srv = AppServer(_app(), host="127.0.0.1", port=0,
                    ssl_context=ssl_context_from(str(cert), str(key))
                    ).start_background()
    try:
        ctx = ssl.create_default_context()
        ctx.check_hostname = False
        ctx.verify_mode = ssl.CERT_NONE
        conn = http.client.HTTPSConnection("127.0.0.1", srv.port,
                                           timeout=10, context=ctx)
        for i in range(3):  # kept alive through the wrapped socket too
            conn.request("POST", "/echo", body=b"x" * 5000,
                         headers={"Expect": "100-continue"})
            resp = conn.getresponse()
            assert resp.status == 200 and json.loads(resp.read())["n"] == 5000
        conn.close()
    finally:
        srv.shutdown()


def test_a_response_is_one_sendall(server, monkeypatch):
    """The first fact the gain rests on: status line, headers and
    payload leave in ONE system call (each one gives the interpreter
    lock up, and in a full interpreter the thread queues for it again)."""
    writes = []

    def counting(name):
        real = getattr(socket.socket, name)

        def call(sock, *args, **kwargs):
            if sock.getsockname()[1] == server.port:  # the server's end
                writes.append((name, len(args[0])))
            return real(sock, *args, **kwargs)
        return call

    for name in ("sendall", "send", "sendmsg", "sendto"):
        monkeypatch.setattr(socket.socket, name, counting(name))
    with _connect(server) as s, s.makefile("rb") as f:
        sizes = []
        for body in (b"", b"x" * 100, b"y" * 20000):
            s.send(_post(body))
            status, headers, answer = _read_response(f)
            sizes.append(len(status) + 2 + sum(
                len(k) + len(v) + 4 for k, v in headers) + 2 + len(answer))
        s.send(b"PATCH /echo HTTP/1.1\r\n\r\n")  # the loop's own answer too
        assert _read_response(f)[0].startswith("HTTP/1.1 501")
    assert [name for name, _ in writes] == ["sendall"] * 4
    assert [n for _, n in writes[:3]] == sizes


def test_a_request_id_costs_no_system_call(server, monkeypatch):
    """The second: ids come from a generator seeded once at start-up,
    not from ``getrandom`` a request."""
    def no_more_entropy(n):
        raise AssertionError("os.urandom called on the request path")

    other = HTTPApp("other")  # seeded before the entropy goes away
    monkeypatch.setattr(os, "urandom", no_more_entropy)
    ids = []
    with _connect(server) as s, s.makefile("rb") as f:
        for _ in range(50):
            s.sendall(_post(b""))
            _, headers, body = _read_response(f)
            ids.append(dict(headers)["X-Request-ID"])
            assert json.loads(body)["id"] == ids[-1]
        s.sendall(_post(b"", "X-Request-ID: from-the-client"))
        _, headers, body = _read_response(f)
        assert dict(headers)["X-Request-ID"] == "from-the-client"
        assert json.loads(body)["id"] == "from-the-client"
    assert all(re.fullmatch(r"[0-9a-f]{16}", i) for i in ids)
    assert len(set(ids)) == 50
    req = Request("GET", "/", {}, {}, b"")
    other.handle(req)  # two apps, two sequences
    assert re.fullmatch(r"[0-9a-f]{16}", req.request_id)
    assert req.request_id not in ids


def test_no_stdlib_request_handler_under_a_handler_thread(server):
    """No ``http.server`` request handler and no ``email`` parser on a
    request's path: the frames of a handler thread inside the app."""
    import traceback
    frames = []

    @server.app.route("GET", "/frames")
    def where(req: Request):
        frames.extend(f.filename for f in traceback.extract_stack())
        return json_response({})

    try:
        with _connect(server) as s, s.makefile("rb") as f:
            s.sendall(b"GET /frames HTTP/1.1\r\nHost: t\r\n\r\n")
            assert _read_response(f)[0] == "HTTP/1.1 200 OK"
    finally:
        server.app._routes.pop()
    assert frames
    assert not [f for f in frames
                if f.endswith(os.sep + "http" + os.sep + "server.py")
                or os.sep + "email" + os.sep in f]
