"""Unit tests for the event-loop serving tier (net/aio_server.py):
the App contract, keep-alive connection handling, the slowloris
header-timeout guard, door-shed at max_connections, sendfile body
serving, async-native dispatch parked on the loop, and the torn
connection (kill simulation) path."""

import json
import socket
import threading
import time
import urllib.request

import pytest

from presto_tpu.config import NetConfig
from presto_tpu.net import M_SENDFILE_BYTES
from presto_tpu.net.aio_server import (AioHttpServer, Response,
                                       SendFile, json_response)

FAST_NET = NetConfig(executor_workers=2, header_timeout_s=0.3,
                     idle_timeout_s=2.0)


class EchoApp:
    """Exercises every Response shape the real servers use."""

    def __init__(self, payload_path=None):
        self.payload_path = payload_path
        self.wake = None

    def handle(self, req):
        if req.path == "/torn":
            return None
        if req.path == "/frames":
            return Response(200, [b"part-a|", b"part-b|", b"part-c"])
        if req.path == "/file":
            import os
            size = os.path.getsize(self.payload_path)
            return Response(200, SendFile(self.payload_path, 0, size),
                            content_type="application/octet-stream")
        if req.path == "/boom":
            raise RuntimeError("handler bug")
        return json_response(200, {"path": req.path,
                                   "method": req.method,
                                   "body": req.body.decode()})

    def dispatch_async(self, req, server):
        if req.path == "/park":
            return self._park(server)
        if req.path == "/slow-snapshot":
            return self._slow_snapshot(server)
        return None

    async def _park(self, server):
        evt, wake = server.waiter()
        self.wake = wake
        await evt.wait()
        return json_response(200, {"woke": True})

    async def _slow_snapshot(self, server):
        # the statement/worker servers dispatch their /v1/metrics and
        # /v1/status renders this same way: one blocking render step
        # pushed to the executor so the loop stays free
        def render():
            time.sleep(0.8)
            return json_response(200, {"scrape": "done"})
        return await server.run_blocking(render)


@pytest.fixture
def served(tmp_path):
    servers = []

    def make(net_config=FAST_NET):
        payload = tmp_path / "payload.bin"
        payload.write_bytes(b"\xabZ" * 8192)        # 16 KiB
        app = EchoApp(payload_path=str(payload))
        srv = AioHttpServer(app, "127.0.0.1", 0, role="test",
                            net_config=net_config).start()
        servers.append(srv)
        return app, srv, f"http://127.0.0.1:{srv.port}"

    yield make
    for srv in servers:
        srv.shutdown()
        srv.server_close()


def _connect(srv):
    s = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
    s.settimeout(5)
    return s


def _raw_get(sock, path):
    sock.sendall(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    return _read_response(sock)


def _read_response(sock):
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(4096)
        if not chunk:
            return None, None, buf
        buf += chunk
    head, _, body = buf.partition(b"\r\n\r\n")
    lines = head.decode().split("\r\n")
    status = int(lines[0].split()[1])
    headers = {k.lower(): v for k, v in
               (ln.split(": ", 1) for ln in lines[1:])}
    n = int(headers.get("content-length", 0))
    while len(body) < n:
        chunk = sock.recv(4096)
        if not chunk:
            break
        body += chunk
    return status, headers, body


def test_roundtrip_and_keepalive_same_socket(served):
    app, srv, base = served()
    s = _connect(srv)
    try:
        st, hdrs, body = _raw_get(s, "/one")
        assert st == 200
        assert json.loads(body)["path"] == "/one"
        # second request on the SAME socket — keep-alive honored
        st, _, body = _raw_get(s, "/two")
        assert st == 200
        assert json.loads(body)["path"] == "/two"
    finally:
        s.close()
    stats = srv.stats()
    assert stats["impl"] == "aio"
    assert stats["connectionsAccepted"] == 1    # one socket, two requests
    assert stats["requestsServed"] == 2


def test_post_body_delivered_to_handler(served):
    app, srv, base = served()
    req = urllib.request.Request(f"{base}/echo", data=b"hello body",
                                 method="POST")
    with urllib.request.urlopen(req, timeout=5) as resp:
        payload = json.loads(resp.read())
    assert payload == {"path": "/echo", "method": "POST",
                       "body": "hello body"}


def test_slowloris_partial_headers_cut_at_timeout(served):
    """Headers trickling slower than header_timeout_s get the
    connection cut — the loop never parks forever on a half-request."""
    app, srv, base = served()
    s = _connect(srv)
    try:
        s.sendall(b"GET /slow HTTP/1.1\r\nHost: t\r\n")  # never finishes
        t0 = time.monotonic()
        assert s.recv(4096) == b""          # server closed on us
        dt = time.monotonic() - t0
        assert dt < 2.0                     # header clock, not idle clock
    finally:
        s.close()


def test_idle_keepalive_socket_reaped(served):
    """A connection that goes quiet between requests is reaped on the
    idle clock (idle_timeout_s), not the tight header clock."""
    cfg = NetConfig(executor_workers=2, header_timeout_s=0.2,
                    idle_timeout_s=0.5)
    app, srv, base = served(cfg)
    s = _connect(srv)
    try:
        st, _, _ = _raw_get(s, "/warm")
        assert st == 200
        t0 = time.monotonic()
        assert s.recv(4096) == b""          # reaped while idle
        assert 0.3 <= time.monotonic() - t0 < 3.0
    finally:
        s.close()


def test_sendfile_body_served_byte_exact(served):
    app, srv, base = served()
    before = M_SENDFILE_BYTES.value()
    with urllib.request.urlopen(f"{base}/file", timeout=5) as resp:
        body = resp.read()
        assert resp.headers["Content-Type"] == "application/octet-stream"
    assert body == b"\xabZ" * 8192
    # the loop thread counts the bytes after loop.sendfile returns,
    # which may be after the client has read the last of them: wait for
    # the count, not for the clock. >= not ==: the counter is global and
    # straggler result serving from earlier tests' clusters can add to
    # it concurrently
    deadline = time.monotonic() + 60
    while (M_SENDFILE_BYTES.value() < before + len(body)
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert M_SENDFILE_BYTES.value() >= before + len(body)


def test_frame_list_body_written_without_join(served):
    app, srv, base = served()
    with urllib.request.urlopen(f"{base}/frames", timeout=5) as resp:
        assert resp.read() == b"part-a|part-b|part-c"
        assert resp.headers["Content-Length"] == "20"


def test_async_dispatch_parks_on_loop_until_woken(served):
    """An async-native route parks on server.waiter() without holding
    any thread; a cross-thread wake() releases it."""
    app, srv, base = served()
    results = []

    def poll():
        with urllib.request.urlopen(f"{base}/park", timeout=10) as r:
            results.append(json.loads(r.read()))

    t = threading.Thread(target=poll, daemon=True)
    t.start()
    deadline = time.monotonic() + 5
    while app.wake is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert app.wake is not None
    assert not results                      # still parked
    app.wake()                              # threadsafe wake from outside
    t.join(timeout=5)
    assert results == [{"woke": True}]
    assert srv.stats()["asyncServed"] == 1
    assert srv.stats()["executorDispatched"] == 0


def test_slow_scrape_does_not_stall_concurrent_long_poll(served):
    """Regression guard for the off-loop snapshot dispatch: a slow
    /v1/metrics-style render (run_blocking, 0.8s of blocking work)
    must not stall a concurrent long-poll on the same server — the
    parked client wakes and completes while the scrape is still
    rendering on the executor."""
    app, srv, base = served()
    slow_done = []
    poll_done = []

    def slow():
        with urllib.request.urlopen(f"{base}/slow-snapshot",
                                    timeout=10) as r:
            slow_done.append((json.loads(r.read()), time.monotonic()))

    def poll():
        with urllib.request.urlopen(f"{base}/park", timeout=10) as r:
            poll_done.append((json.loads(r.read()), time.monotonic()))

    ts = threading.Thread(target=slow, daemon=True)
    ts.start()
    tp = threading.Thread(target=poll, daemon=True)
    tp.start()
    deadline = time.monotonic() + 5
    while app.wake is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert app.wake is not None, \
        "long-poll never reached the loop — scrape blocked it"
    app.wake()
    tp.join(timeout=5)
    assert poll_done and poll_done[0][0] == {"woke": True}
    assert not slow_done, \
        "long-poll should complete while the scrape still renders"
    ts.join(timeout=5)
    assert slow_done and slow_done[0][0] == {"scrape": "done"}
    assert poll_done[0][1] < slow_done[0][1]


def test_handler_exception_surfaces_as_500(served):
    app, srv, base = served()
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{base}/boom", timeout=5)
    assert ei.value.code == 500
    assert "handler bug" in json.loads(ei.value.read())["error"]


def test_torn_response_closes_without_bytes(served):
    """handle() returning None is the kill simulation: the connection
    tears with NO response bytes — the client sees a dead peer, never
    a half-truth."""
    app, srv, base = served()
    s = _connect(srv)
    try:
        s.sendall(b"GET /torn HTTP/1.1\r\nHost: t\r\n\r\n")
        assert s.recv(4096) == b""
    finally:
        s.close()


def test_max_connections_door_shed(served):
    """Connections beyond max_connections are closed at the door while
    the ones inside keep working."""
    cfg = NetConfig(executor_workers=2, header_timeout_s=0.3,
                    idle_timeout_s=5.0, max_connections=1)
    app, srv, base = served(cfg)
    first = _connect(srv)
    try:
        st, _, _ = _raw_get(first, "/inside")     # occupies the one slot
        assert st == 200
        shed = _connect(srv)
        try:
            shed.sendall(b"GET /shed HTTP/1.1\r\nHost: t\r\n\r\n")
            try:
                # shed at the door: EOF, or RST if the close beat our
                # request bytes to the server
                assert shed.recv(4096) == b""
            except ConnectionResetError:
                pass
        finally:
            shed.close()
        st, _, _ = _raw_get(first, "/still-inside")
        assert st == 200                          # survivor unaffected
    finally:
        first.close()


def test_bad_request_line_gets_400(served):
    app, srv, base = served()
    s = _connect(srv)
    try:
        s.sendall(b"NOT-HTTP\r\n\r\n")
        data = s.recv(4096)
        assert data.startswith(b"HTTP/1.1 400")
    finally:
        s.close()
