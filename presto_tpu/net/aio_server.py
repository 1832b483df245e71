"""Event-loop HTTP server — the front door for BOTH node roles.

Reference: http/HttpServer.cpp in the native worker (libevent loop
serving the task/result protocol) and the Jetty selector threads under
the Java coordinator. The protocol surface this engine serves is
long-poll shaped end to end — statement nextUri GETs, task status
polls, result-page GETs all park until data exists — and a
thread-per-connection shell pins one OS thread per parked poll. Here a
parked long-poll costs one coroutine.

Architecture:

  * the listening socket is bound synchronously in the constructor, so
    ``.port`` is known before ``start()`` and early clients queue in
    the accept backlog;
  * ONE spawned thread runs the asyncio loop; requests are parsed on
    the loop with a slowloris header timeout;
  * dispatch splits two ways: routes the app serves natively async
    (statement POST, nextUri GET, task-results long-poll) run as
    coroutines on the loop; everything else runs the app's sync
    ``handle()`` inside a bounded ThreadPoolExecutor, so blocking work
    never lands on the loop and the process thread count stays flat
    under any connection count;
  * zero-copy responses: a ``SendFile`` body goes out through
    ``loop.sendfile`` (kernel sendfile when the transport allows;
    counted in ``presto_tpu_net_sendfile_bytes_total``), and
    list-of-frames bodies are written frame by frame — never
    ``b"".join``-copied.

The App contract:

  handle(request) -> Response | None     sync router; None = tear the
                                         connection with no response
                                         (coordinator kill simulation)
  dispatch_async(request, server)        optional; a coroutine for hot
      -> coroutine | None                paths, None = use handle()

A failure matrix note for operators lives in README "Serving tier".
"""

from __future__ import annotations

import asyncio
import json
import socket
import threading
from concurrent.futures import ThreadPoolExecutor
from http import HTTPStatus
from typing import Dict, List, Optional, Union

from presto_tpu.config import DEFAULT_NET, NetConfig
from presto_tpu.net import (
    M_CONNECTIONS_OPENED, M_KEEPALIVE_REUSE, M_LOOP_LAG,
    M_OPEN_CONNECTIONS, M_SENDFILE_BYTES,
)
from presto_tpu.utils.threads import spawn

_HEAD_END = b"\r\n\r\n"


class Headers:
    """Case-insensitive request/response header map (last value wins),
    mirroring the lookups handler code does on email.message.Message."""

    __slots__ = ("_d",)

    def __init__(self, items=()):
        self._d: Dict[str, str] = {}
        for k, v in items:
            self._d[k.lower()] = v

    def set(self, name: str, value: str) -> None:
        self._d[name.lower()] = value

    def get(self, name: str, default=None):
        return self._d.get(name.lower(), default)

    def __contains__(self, name: str) -> bool:
        return name.lower() in self._d

    def items(self):
        return self._d.items()


class Request:
    """One parsed HTTP request."""

    __slots__ = ("method", "target", "path", "headers", "body")

    def __init__(self, method: str, target: str, headers: Headers,
                 body: bytes = b""):
        self.method = method
        self.target = target
        self.path = target.split("?")[0]
        self.headers = headers
        self.body = body


class SendFile:
    """A zero-copy response body: `count` bytes of `path` starting at
    `offset`, shipped via loop.sendfile."""

    __slots__ = ("path", "offset", "count")

    def __init__(self, path: str, offset: int, count: int):
        self.path = path
        self.offset = offset
        self.count = count


#: response body forms: bytes, a list of frames (written without a
#: join copy), or a spool file range
Body = Union[bytes, List[bytes], SendFile]


class Response:
    """Status + headers + body; the server owns framing (Content-Length
    is always computed here, so clients can frame on it)."""

    __slots__ = ("status", "body", "headers", "content_type")

    def __init__(self, status: int = 200, body: Body = b"",
                 headers: Optional[dict] = None,
                 content_type: str = "application/json"):
        self.status = status
        self.body = body
        self.headers = dict(headers or {})
        self.content_type = content_type

    def body_length(self) -> int:
        b = self.body
        if isinstance(b, SendFile):
            return b.count
        if isinstance(b, (list, tuple)):
            return sum(len(f) for f in b)
        return len(b)


def json_response(status: int, obj, headers: Optional[dict] = None
                  ) -> Response:
    return Response(status, json.dumps(obj).encode(), headers=headers)


def render_head(resp: Response, keep_alive: bool,
                server_name: str) -> bytes:
    """Serialize the status line + headers."""
    try:
        reason = HTTPStatus(resp.status).phrase
    except ValueError:
        reason = "Unknown"
    lines = [f"HTTP/1.1 {resp.status} {reason}",
             f"Server: {server_name}"]
    if resp.status not in (204, 304):
        lines.append(f"Content-Type: {resp.content_type}")
        lines.append(f"Content-Length: {resp.body_length()}")
    lines.append(
        f"Connection: {'keep-alive' if keep_alive else 'close'}")
    for k, v in resp.headers.items():
        lines.append(f"{k}: {v}")
    return ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1")


class AioHttpServer:
    """One event-loop HTTP server serving an App.

    Exposes the same hard-kill surface the ThreadingHTTPServer shell
    did (`shutdown()` / `server_close()` / a `dead` flag apps consult),
    so chaos helpers that tear a node down keep working unchanged."""

    def __init__(self, app, host: str = "127.0.0.1", port: int = 0,
                 role: str = "server",
                 net_config: Optional[NetConfig] = None):
        self.app = app
        self.role = role
        self.cfg = net_config if net_config is not None else DEFAULT_NET
        self._sock = socket.create_server((host, port), backlog=512)
        self.server_address = self._sock.getsockname()
        self.port = self.server_address[1]
        self.loop = asyncio.new_event_loop()
        self.executor = ThreadPoolExecutor(
            max_workers=self.cfg.executor_workers,
            thread_name_prefix=f"presto-tpu-net-{role}-exec")
        #: coordinator kill simulation: in-flight handlers observe this
        #: and tear their connections instead of answering
        self.dead = False
        self._stop_evt: Optional[asyncio.Event] = None
        self._started = threading.Event()
        self._conn_tasks: set = set()
        self._open = 0
        self.requests_served = 0
        self.async_served = 0
        self.executor_dispatched = 0
        self.connections_accepted = 0
        self._thread = spawn("net", f"{role}-loop", self._run,
                             start=False)
        self._closed = False

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "AioHttpServer":
        self._thread.start()
        if not self._started.wait(timeout=10.0):
            raise RuntimeError("event loop failed to start")
        return self

    def serve_forever(self) -> None:
        """ThreadingHTTPServer-shaped alias: start and block until
        shutdown() (the worker/coordinator shells spawn this)."""
        self.start()
        self._thread.join()

    def shutdown(self) -> None:
        """Stop serving NOW: cancel every in-flight connection task (a
        parked long-poll's client sees a torn connection, exactly like
        a killed thread-per-connection server) and stop the loop."""
        if self._stop_evt is not None and not self.loop.is_closed():
            try:
                self.loop.call_soon_threadsafe(self._stop_evt.set)
            except RuntimeError:
                pass
        if self._thread.is_alive() \
                and self._thread is not threading.current_thread():
            self._thread.join(timeout=10.0)
        self.executor.shutdown(wait=False)

    def server_close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._sock.close()
        except OSError:
            pass

    # --------------------------------------------------------------- loop
    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)
        try:
            self.loop.run_until_complete(self._main())
        finally:
            try:
                self.loop.close()
            except RuntimeError:
                pass

    async def _main(self) -> None:
        self._stop_evt = asyncio.Event()
        server = await asyncio.start_server(
            self._serve_connection, sock=self._sock)
        lag_task = self.loop.create_task(self._lag_heartbeat())
        self._started.set()
        await self._stop_evt.wait()
        lag_task.cancel()
        server.close()
        for t in list(self._conn_tasks):
            t.cancel()
        await asyncio.gather(lag_task, *list(self._conn_tasks),
                             return_exceptions=True)
        try:
            await server.wait_closed()
        except Exception:  # noqa: BLE001 — already tearing down
            pass

    async def _lag_heartbeat(self) -> None:
        """Blocked-loop detector: measure how late a fixed-interval
        timer fires. Anything blocking the loop shows up here as lag."""
        tick = self.cfg.loop_lag_tick_s
        while True:
            t0 = self.loop.time()
            await asyncio.sleep(tick)
            M_LOOP_LAG.observe(max(0.0, self.loop.time() - t0 - tick))

    # --------------------------------------------------------- connections
    async def _serve_connection(self, reader: asyncio.StreamReader,
                                writer: asyncio.StreamWriter) -> None:
        task = asyncio.current_task()
        self._conn_tasks.add(task)
        if self._open >= self.cfg.max_connections:
            # pool exhaustion is shed at the door: close immediately
            # instead of queueing unbounded connections into memory
            self._conn_tasks.discard(task)
            writer.close()
            return
        self._open += 1
        self.connections_accepted += 1
        M_OPEN_CONNECTIONS.set(self._open, role=self.role)
        M_CONNECTIONS_OPENED.inc(role=self.role)
        try:
            await self._connection_loop(reader, writer)
        except (ConnectionError, asyncio.CancelledError, OSError):
            pass
        finally:
            self._open -= 1
            M_OPEN_CONNECTIONS.set(self._open, role=self.role)
            self._conn_tasks.discard(task)
            try:
                writer.close()
            except Exception:  # noqa: BLE001 — transport already dead
                pass

    async def _connection_loop(self, reader, writer) -> None:
        cfg = self.cfg
        served = 0
        while True:
            # keep-alive idle wait for the first byte, THEN the
            # slowloris clock: complete headers must arrive within
            # header_timeout_s of the first byte or the connection dies
            try:
                first = await asyncio.wait_for(
                    reader.read(1), timeout=cfg.idle_timeout_s)
            except asyncio.TimeoutError:
                return
            if not first:
                return                        # clean client close
            try:
                rest = await asyncio.wait_for(
                    reader.readuntil(_HEAD_END),
                    timeout=cfg.header_timeout_s)
            except (asyncio.TimeoutError, asyncio.IncompleteReadError,
                    asyncio.LimitOverrunError):
                return                        # slowloris / torn / huge
            req = _parse_request(first + rest)
            if req is None:
                writer.write(render_head(
                    Response(400, b""), False, self._server_name()))
                await writer.drain()
                return
            n = int(req.headers.get("Content-Length", 0) or 0)
            if n:
                try:
                    req.body = await asyncio.wait_for(
                        reader.readexactly(n),
                        timeout=cfg.header_timeout_s)
                except (asyncio.TimeoutError,
                        asyncio.IncompleteReadError):
                    return
            if served:
                M_KEEPALIVE_REUSE.inc(role=self.role)
            resp = await self._dispatch(req)
            if resp is None:
                return              # kill simulation: torn, no response
            keep = _wants_keep_alive(req)
            await self._write_response(writer, resp, keep)
            served += 1
            self.requests_served += 1
            if not keep:
                return

    async def _dispatch(self, req: Request) -> Optional[Response]:
        try:
            coro = None
            da = getattr(self.app, "dispatch_async", None)
            if da is not None:
                coro = da(req, self)
            if coro is not None:
                self.async_served += 1
                return await coro
            self.executor_dispatched += 1
            return await self.loop.run_in_executor(
                self.executor, self.app.handle, req)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — a handler bug must not
            # kill the connection loop; surface it as a plain 500
            return json_response(
                500, {"error": f"{type(e).__name__}: {e}"[:500]})

    def _server_name(self) -> str:
        return f"presto-tpu-{self.role}"

    async def _write_response(self, writer, resp: Response,
                              keep_alive: bool) -> None:
        body = resp.body
        writer.write(render_head(resp, keep_alive, self._server_name()))
        if resp.status in (204, 304):
            await writer.drain()
            return
        if isinstance(body, SendFile):
            await writer.drain()
            if body.count > 0:
                with open(body.path, "rb") as f:
                    sent = await self.loop.sendfile(
                        writer.transport, f, offset=body.offset,
                        count=body.count, fallback=True)
                M_SENDFILE_BYTES.inc(sent)
        elif isinstance(body, (list, tuple)):
            for frame in body:        # no b"".join copy
                writer.write(frame)
        elif body:
            writer.write(body)
        await writer.drain()

    # ------------------------------------------------------------ app API
    def run_blocking(self, fn, *args):
        """Awaitable executor dispatch for async handlers that need one
        blocking step (spool reads, SMILE encodes)."""
        return self.loop.run_in_executor(self.executor, fn, *args)

    def waiter(self):
        """(asyncio.Event, threadsafe-wake-callable) pair: async
        long-poll handlers hand the callable to threading-world code
        (buffer managers, query done hooks) and await the event."""
        evt = asyncio.Event()

        def wake() -> None:
            try:
                self.loop.call_soon_threadsafe(evt.set)
            except RuntimeError:
                pass                     # loop already gone
        return evt, wake

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Connection + loop stats block for GET /v1/status."""
        return {
            "impl": "aio",
            "openConnections": self._open,
            "connectionsAccepted": self.connections_accepted,
            "requestsServed": self.requests_served,
            "asyncServed": self.async_served,
            "executorDispatched": self.executor_dispatched,
            "executorWorkers": self.cfg.executor_workers,
            "loopLagTicks": M_LOOP_LAG.count(),
        }


def _wants_keep_alive(req: Request) -> bool:
    conn = (req.headers.get("Connection", "") or "").lower()
    return conn != "close"


def _parse_request(head: bytes) -> Optional[Request]:
    try:
        text = head.decode("latin-1")
        lines = text.split("\r\n")
        method, target, _version = lines[0].split(" ", 2)
    except (UnicodeDecodeError, ValueError):
        return None
    headers = Headers()
    for ln in lines[1:]:
        if not ln:
            continue
        name, sep, value = ln.partition(":")
        if not sep:
            return None
        headers.set(name.strip(), value.strip())
    return Request(method, target, headers)
