"""Load generator children. Imports neither JAX nor the program.

One child is one process with ``connections`` keep-alive HTTP/1.1
connections, one sender thread to a connection. The arithmetic is that
of ``benchmarks/_loadgen.py``: an open loop times request ``k`` from the
instant it was DUE (``t0 + due[k]``), whatever the server or the
senders were doing, so a stall is charged to every arrival it delays; a
closed loop times from the send. No request is sent before ``t0``, the
opening of the window. Children report how late they sent.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

HEADERS = {"Content-Type": "application/json"}


def post(conn, body: bytes, num: int):
    """One query; returns the payload, or raises on a failed or
    malformed answer (a wrong answer counts as missing)."""
    try:
        conn.request("POST", "/queries.json", body=body, headers=HEADERS)
        resp = conn.getresponse()
        payload = resp.read()
    except Exception:
        conn.close()  # http.client reconnects on the next request
        raise
    if resp.status != 200:
        raise RuntimeError(f"status {resp.status}")
    scores = json.loads(payload).get("itemScores")
    if not isinstance(scores, list) or len(scores) != num:
        raise RuntimeError("answer without %d item scores" % num)
    return payload


def child_main(pipe, spec: dict) -> None:
    """``spec``: connections, num, bodies (list of bytes), due (offsets
    in seconds: an open loop; absent: a closed loop), keep (indices
    whose payload is sent back for the output check). The parent sends
    the port once the server listens, then ``(t0, seconds)`` on the
    system-wide monotonic clock."""
    num = spec["num"]
    bodies, keep = spec["bodies"], set(spec["keep"])
    n = len(bodies)
    port = pipe.recv()
    conns = []
    for _ in range(spec["connections"]):
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        c.connect()
        conns.append(c)
    pipe.send("ready")
    t0, seconds = pipe.recv()
    t_end = t0 + seconds
    time.sleep(max(t0 - time.monotonic(), 0.0))  # the window opens at t0
    lat = [None] * n      # seconds, None = not sent, -1.0 = failed
    late = [0.0] * n      # actual minus due send, open loop
    done = [0.0] * n      # completion offset from t0
    kept, errors = {}, []
    lock = threading.Lock()
    nxt = iter(range(n))
    due = spec.get("due")

    def sender(conn) -> None:
        while True:
            with lock:
                k = next(nxt, None)
            if k is None:
                return
            if due is not None:
                t_ref = t0 + due[k]
                wait = t_ref - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                late[k] = time.monotonic() - t_ref
            else:
                t_ref = time.monotonic()
                if t_ref >= t_end:
                    return
            try:
                payload = post(conn, bodies[k], num)
            except Exception as e:  # noqa: BLE001 - counted, reported
                lat[k] = -1.0
                with lock:
                    if len(errors) < 5:
                        errors.append(f"{type(e).__name__}: {e}"[:200])
                continue
            finally:
                done[k] = time.monotonic() - t0
            lat[k] = time.monotonic() - t_ref
            if k in keep:
                kept[k] = payload.decode()

    threads = [threading.Thread(target=sender, args=(c,), daemon=True)
               for c in conns]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for c in conns:
        c.close()
    pipe.send({"lat": lat, "late": late, "done": done, "kept": kept,
               "errors": errors})
    pipe.close()
