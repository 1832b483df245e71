"""Worker HTTP endpoints — the exact surface the coordinator drives.

Reference: presto_cpp/main/TaskResource.cpp:115-180 (regex-routed task
endpoints), PrestoServer.cpp:497-562 (/v1/info, /v1/info/state,
/v1/status, /v1/memory), http/HttpServer.cpp. The shell is the
`net/aio_server` event loop (the same libevent-shaped front door the
native worker uses): requests parse on the loop, the long-poll hot
paths (results GET, status GET) run natively async so a parked poll
costs a coroutine, and every other route dispatches the sync
`WorkerApp.handle` through the loop's bounded executor. Routes,
headers and long-poll semantics are byte-for-byte the old ones:

  POST   /v1/task/{id}                          TaskUpdateRequest -> TaskInfo
  GET    /v1/task/{id}                          TaskInfo
  GET    /v1/task/{id}/status                   TaskStatus (long-poll)
  GET    /v1/task/{id}/results/{buffer}/{token} SerializedPage frames
  GET    /v1/task/{id}/results/{buffer}/{token}/acknowledge
  DELETE /v1/task/{id}/results/{buffer}         abort buffer
  DELETE /v1/task/{id}                          delete task
  GET    /v1/info | /v1/info/state | /v1/status | /v1/memory

Page-stream headers (reference PrestoHeaders.java:51-54):
  X-Presto-Page-Sequence-Id / X-Presto-Page-End-Sequence-Id /
  X-Presto-Buffer-Complete / X-Presto-Task-Instance-Id
"""

from __future__ import annotations

import asyncio
import json
import re
import time
from typing import Optional

import presto_tpu.exec.dist_executor  # noqa: F401 — registers mesh metrics
from presto_tpu.config import DEFAULT_NET
from presto_tpu.net.aio_server import (
    AioHttpServer, Request, Response, SendFile,
)
from presto_tpu.obs.metrics import gauge as _gauge
from presto_tpu.protocol import structs as S
from presto_tpu.server.buffers import BufferClosedError
from presto_tpu.server.task_manager import (
    TpuTaskManager, WorkerDrainingError,
)
from presto_tpu.utils.tracing import (
    TRACE_HEADER, TRACER, parse_trace_header,
)

_M_UPTIME = _gauge("presto_tpu_uptime_seconds",
                   "Seconds since this server process started serving")

#: Prometheus exposition content type (text format 0.0.4)
PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4"

_TASK = re.compile(r"^/v1/task/([^/?]+)$")
_STATUS = re.compile(r"^/v1/task/([^/?]+)/status$")
_RESULTS = re.compile(r"^/v1/task/([^/?]+)/results/([^/]+)/(\d+)$")
_ACK = re.compile(r"^/v1/task/([^/?]+)/results/([^/]+)/(\d+)/acknowledge$")
_ABORT = re.compile(r"^/v1/task/([^/?]+)/results/([^/]+)$")
_BATCH = re.compile(r"^/v1/task/([^/?]+)/batch$")
_REMOTE_SOURCE = re.compile(
    r"^/v1/task/([^/?]+)/remote-source/([^/?]+)$")
_TRACE = re.compile(r"^/v1/trace/([^/?]+)$")

_SERVER_START = time.time()

#: async status long-poll re-check cadence (state transitions already
#: fire the task's state_change Condition for threaded waiters; the
#: loop-side poll keeps the async path lock-free)
_STATUS_POLL_S = 0.02


def _parse_duration(s: Optional[str], default: float) -> float:
    if not s:
        return default
    m = re.match(r"([\d.]+)\s*(ms|s|m)?", s)
    if not m:
        return default
    v = float(m.group(1))
    unit = m.group(2) or "s"
    return v / 1000 if unit == "ms" else v * 60 if unit == "m" else v


def _parse_size(s: Optional[str], default: int) -> int:
    """X-Presto-Max-Size: '16MB' / '1048576B' / '512kB' -> bytes."""
    if not s:
        return default
    m = re.match(r"([\d.]+)\s*(B|kB|MB|GB)?", s)
    if not m:
        return default
    v = float(m.group(1))
    unit = m.group(2) or "B"
    return int(v * {"B": 1, "kB": 1 << 10, "MB": 1 << 20,
                    "GB": 1 << 30}[unit])


def _json_response(req: Request, code: int, obj, headers=None
                   ) -> Response:
    """Protocol-document response. Binary transport negotiation
    (reference: InternalCommunicationConfig.java:174
    isBinaryTransportEnabled): a client that Accepts
    application/x-jackson-smile gets the same document SMILE-encoded."""
    from presto_tpu.protocol import smile
    accept = req.headers.get("Accept", "") or ""
    if smile.CONTENT_TYPE in accept:
        return Response(code, smile.dumps(obj), headers=headers,
                        content_type=smile.CONTENT_TYPE)
    return Response(code, json.dumps(obj).encode(), headers=headers)


def _pages_response(code: int, body, headers=None) -> Response:
    """Page-stream response; `body` may be bytes, a frame list
    (written without a join copy) or a SendFile spool range."""
    return Response(code, body, headers=headers,
                    content_type="application/x-presto-pages")


def _read_body_doc(req: Request):
    """Request body -> JSON-compatible document; SMILE bodies are
    negotiated via Content-Type, JSON stays the default."""
    from presto_tpu.protocol import smile
    ctype = req.headers.get("Content-Type", "") or ""
    if smile.CONTENT_TYPE in ctype:
        return smile.loads(req.body)
    return json.loads(req.body.decode())


class WorkerApp:
    """The worker's request router, served by AioHttpServer. Sync
    routes run on the loop's bounded executor via `handle`; the
    long-poll hot paths are served natively async via
    `dispatch_async` — a parked results/status poll holds no thread."""

    def __init__(self):
        self.task_manager: Optional[TpuTaskManager] = None
        self.authenticator = None
        self.worker_server = None
        self.httpd: Optional[AioHttpServer] = None

    @property
    def tm(self) -> TpuTaskManager:
        return self.task_manager

    def _authorized(self, req: Request) -> Optional[Response]:
        """Internal JWT gate (InternalAuthenticationManager.java:
        authenticateInternalRequest) — applies to every route when a
        shared secret is configured. Returns the 401 to send, or None
        when the request may proceed."""
        if self.authenticator is None:
            return None
        from presto_tpu.server.auth import (
            AuthenticationError, PRESTO_INTERNAL_BEARER,
        )
        token = req.headers.get(PRESTO_INTERNAL_BEARER)
        if not token:
            return _json_response(
                req, 401, {"error": "missing internal bearer token"})
        try:
            self.authenticator.authenticate(token)
            return None
        except AuthenticationError as e:
            return _json_response(req, 401, {"error": str(e)})

    # -------------------------------------------------- async hot paths
    def dispatch_async(self, req: Request, server: AioHttpServer):
        """Coroutine for the long-poll hot paths, None for everything
        else (which then rides the executor)."""
        if req.method != "GET":
            return None
        m = _RESULTS.match(req.path)
        if m:
            return self._results_async(server, req, *m.groups())
        m = _STATUS.match(req.path)
        if m:
            return self._status_async(server, req, m.group(1))
        if req.path in ("/v1/metrics", "/v1/status"):
            return self._snapshot_async(server, req)
        return None

    async def _snapshot_async(self, server: AioHttpServer,
                              req: Request):
        """Scrape-time gauge computation (process gauges, registry
        render, pool/spool snapshots) off the event loop: the
        coordinator's telemetry sweep hits /v1/metrics on the
        heartbeat cadence, and a slow scrape must degrade only the
        scrape — never the long-polls parked on the same loop
        (tests/test_aio_server.py asserts this)."""
        denied = self._authorized(req)
        if denied is not None:
            return denied
        return await server.run_blocking(self._get, req)

    async def _results_async(self, server: AioHttpServer, req: Request,
                             task_id: str, buffer_id: str, token: str):
        denied = self._authorized(req)
        if denied is not None:
            return denied
        task = self.tm.get(task_id)
        if task is None or task.buffers is None:
            return await server.run_blocking(
                self._cold_results, req, task_id, buffer_id, token)
        mgr = task.buffers
        buf = mgr.buffer(buffer_id)
        if buf is None:
            return _json_response(req, 404, {"error": "no buffer"})
        max_bytes = _parse_size(req.headers.get("X-Presto-Max-Size"),
                                16 << 20)
        tok = int(token)
        deadline = server.loop.time() + _parse_duration(
            req.headers.get("X-Presto-Max-Wait"), 1.0)
        evt, wake = server.waiter()
        mgr.add_waker(wake)
        try:
            while True:
                # arm-then-check: the waker is live before the read, so
                # a page arriving during the read sets the event and
                # the wait below returns immediately — no missed wake
                evt.clear()
                try:
                    frames, nxt, complete = await server.run_blocking(
                        buf.get, tok, max_bytes)
                except BufferClosedError:
                    return await server.run_blocking(
                        self._closed_buffer_results, req, task_id,
                        buffer_id, token)
                if frames or complete:
                    break
                remaining = deadline - server.loop.time()
                if remaining <= 0:
                    break
                try:
                    await asyncio.wait_for(evt.wait(),
                                           timeout=remaining)
                except asyncio.TimeoutError:
                    pass
        finally:
            mgr.remove_waker(wake)
        headers = {
            "X-Presto-Task-Instance-Id": str(task.instance_id),
            "X-Presto-Page-Sequence-Id": str(tok),
            "X-Presto-Page-End-Sequence-Id": str(nxt),
            "X-Presto-Buffer-Complete": "true" if complete else "false",
        }
        return _pages_response(200, frames, headers)

    async def _status_async(self, server: AioHttpServer, req: Request,
                            task_id: str):
        denied = self._authorized(req)
        if denied is not None:
            return denied
        cur = req.headers.get("X-Presto-Current-State")
        deadline = server.loop.time() + _parse_duration(
            req.headers.get("X-Presto-Max-Wait"), 1.0)
        while True:
            st = await server.run_blocking(
                self.tm.get_status, task_id, None, 0.0)
            if st is None:
                return _json_response(req, 404, {"error": "no task"})
            if cur is None or st.state != cur \
                    or server.loop.time() >= deadline:
                return _json_response(req, 200,
                                      S.TaskStatus.to_json(st))
            await asyncio.sleep(_STATUS_POLL_S)

    # ------------------------------------------------------ sync router
    def handle(self, req: Request) -> Optional[Response]:
        denied = self._authorized(req)
        if denied is not None:
            return denied
        if req.method == "GET":
            return self._get(req)
        if req.method == "POST":
            return self._post(req)
        if req.method == "PUT":
            return self._put(req)
        if req.method == "DELETE":
            return self._delete(req)
        return _json_response(req, 404,
                              {"error": f"no route {req.path}"})

    # ------------------------------------------------------------- POST
    def _post(self, req: Request) -> Response:
        path = req.path
        trace_ctx = parse_trace_header(req.headers.get(TRACE_HEADER))
        m = _BATCH.match(path)
        if m:
            # /v1/task/{id}/batch (TaskResource.cpp:115-180): unwrap the
            # BatchTaskUpdateRequest envelope; shuffle descriptors are
            # accepted and ignored (no Spark shuffle backend)
            breq = S.BatchTaskUpdateRequest.from_json(
                _read_body_doc(req))
            try:
                info = self.tm.create_or_update(m.group(1),
                                                breq.taskUpdateRequest,
                                                trace_ctx=trace_ctx)
            except WorkerDrainingError as e:
                return self._draining_reject(req, e)
            return _json_response(req, 200, S.TaskInfo.to_json(info))
        m = _TASK.match(path)
        if m:
            ureq = S.TaskUpdateRequest.from_json(_read_body_doc(req))
            try:
                info = self.tm.create_or_update(m.group(1), ureq,
                                                trace_ctx=trace_ctx)
            except WorkerDrainingError as e:
                return self._draining_reject(req, e)
            return _json_response(req, 200, S.TaskInfo.to_json(info))
        return _json_response(req, 404,
                              {"error": f"no route {req.path}"})

    def _draining_reject(self, req: Request,
                         e: WorkerDrainingError) -> Response:
        """410 Gone + X-Presto-Draining: the coordinator reads the
        marker as 'reschedule elsewhere', not as a worker fault — a
        4xx already records breaker success, so a draining node takes
        no availability penalty."""
        return _json_response(req, 410,
                              {"error": str(e), "draining": True},
                              headers={"X-Presto-Draining": "true"})

    # -------------------------------------------------------------- PUT
    def _put(self, req: Request) -> Response:
        """PUT /v1/info/state (reference: PrestoServer.cpp's node-state
        endpoint): body "SHUTTING_DOWN" starts a graceful decommission.
        The drain runs synchronously on this executor thread — new task
        creations are refused from the first instant, running tasks
        finish and commit their spools, then the announcer retracts the
        node before the response returns, so a 200 means the node is
        fully drained (or the drain timeout elapsed)."""
        if req.path != "/v1/info/state":
            return _json_response(req, 404,
                                  {"error": f"no route {req.path}"})
        try:
            want = _read_body_doc(req)
        except Exception:   # noqa: BLE001 — malformed body
            return _json_response(req, 400,
                                  {"error": "unparseable state body"})
        if want != "SHUTTING_DOWN":
            return _json_response(req, 400, {
                "error": f"unsupported state {want!r}; only "
                         f"SHUTTING_DOWN is accepted"})
        ws = self.worker_server
        report = ws.drain() if ws is not None else self.tm.drain()
        return _json_response(req, 200, report)

    # -------------------------------------------------------------- GET
    def _get(self, req: Request) -> Response:
        path = req.path
        m = _ACK.match(path)
        if m:
            task = self.tm.get(m.group(1))
            if task is None or task.buffers is None:
                # a committed spool needs no ack bookkeeping (every
                # token stays replayable) — 200 no-op keeps consumers
                # of spool-served streams on the normal protocol path
                if self._spool_for(m.group(1)) is not None:
                    return _pages_response(200, b"")
                return _json_response(req, 404, {"error": "no task"})
            buf = task.buffers.buffer(m.group(2))
            if buf is not None:
                buf.acknowledge(int(m.group(3)))
            return _pages_response(200, b"")
        m = _RESULTS.match(path)
        if m:
            return self._results(req, *m.groups())
        m = _STATUS.match(path)
        if m:
            cur = req.headers.get("X-Presto-Current-State")
            wait = _parse_duration(
                req.headers.get("X-Presto-Max-Wait"), 1.0)
            st = self.tm.get_status(m.group(1), cur, wait)
            if st is None:
                return _json_response(req, 404, {"error": "no task"})
            return _json_response(req, 200, S.TaskStatus.to_json(st))
        m = _TASK.match(path)
        if m:
            task = self.tm.get(m.group(1))
            if task is None:
                return _json_response(req, 404, {"error": "no task"})
            return _json_response(req, 200, S.TaskInfo.to_json(
                task.info(self.tm.base_uri)))
        if path == "/v1/info":
            return _json_response(req, 200, {
                "nodeVersion": {"version": "presto-tpu-0.2"},
                "environment": "tpu", "coordinator": False,
                "starting": False,
                "uptime": f"{time.time() - _SERVER_START:.2f}s"})
        if path == "/v1/info/state":
            return _json_response(req, 200, self.tm.lifecycle_state)
        if path == "/v1/mesh":
            # cluster mesh tier advertisement (server/mesh_tier.py):
            # probed FRESH by the coordinator per mesh-eligible query —
            # a draining worker has retracted and is never chosen
            return _json_response(req, 200,
                                  self.tm.mesh_tier.advertisement())
        if path == "/v1/status":
            # NodeStatus role (PrestoServer.cpp /v1/status): JSON node
            # snapshot — identity, role, uptime, task counts, heap-proxy
            # byte gauges, serving-tier connection + loop stats
            tasks = self.tm.tasks
            return _json_response(req, 200, {
                "nodeId": self.tm.node_id, "environment": "tpu",
                "role": "worker",
                "uptime": f"{time.time() - _SERVER_START:.2f}s",
                "uptimeSeconds": time.time() - _SERVER_START,
                "externalAddress": "127.0.0.1",
                "internalAddress": "127.0.0.1",
                "taskCount": len(tasks),
                "tasksCreated": self.tm.lifetime_tasks,
                "nodeState": self.tm.lifecycle_state,
                "drain": {
                    "state": self.tm.lifecycle_state,
                    "rejected": self.tm.drain_rejected,
                    "drainSeconds": self.tm.drain_seconds,
                },
                "net": (self.httpd.stats()
                        if self.httpd is not None else {}),
                "memoryInfo": {"availableProcessors": 1},
                "processCpuLoad": 0.0, "systemCpuLoad": 0.0,
                "heapUsed": self.tm.memory_bytes(),
                "heapAvailable": 16 << 30, "nonHeapUsed": 0,
                # worker pool reservations (exec/memory.MemoryPool) —
                # the coordinator's heartbeat scrape aggregates these
                # into the cluster memory view for admission quotas
                "memoryPool": self.tm.pool_stats(),
                # cluster mesh tier: slice advertisement + mesh-lowered
                # task / ICI-exchange tallies (server/mesh_tier.py)
                "clusterMesh": self.tm.mesh_tier.status_block()})
        if path == "/v1/tasks":
            # per-task summary rows — the worker-side feed of
            # system.runtime.tasks (fanned out by the system connector)
            return _json_response(req, 200, self.tm.task_rows())
        if path == "/v1/profile":
            # collapsed-stack text (flamegraph.pl-ready) from the
            # always-on sampling profiler
            from presto_tpu.obs.profiler import PROFILER
            return Response(
                200, (PROFILER.collapsed() + "\n").encode(),
                content_type="text/plain; charset=utf-8")
        if path in ("/v1/metrics", "/v1/info/metrics"):
            # Prometheus text exposition of the process-global registry
            # (reference: presto_cpp/main/runtime-metrics/
            # PrometheusStatsReporter.cpp, registered at
            # PrestoServer.cpp:562). /v1/info/metrics is the legacy
            # alias; scrape-time gauges (worker + process) refresh first
            # inside the shared render_metrics_payload() scrape path.
            from presto_tpu.obs.process import render_metrics_payload
            self.tm.record_gauges()
            _M_UPTIME.set(time.time() - _SERVER_START)
            return Response(200, render_metrics_payload().encode(),
                            content_type=PROMETHEUS_CONTENT_TYPE)
        m = _TRACE.match(path)
        if m:
            # worker span dump the coordinator scrapes at query end to
            # stitch the cross-node timeline
            return _json_response(req, 200, TRACER.to_json(m.group(1)))
        if path == "/v1/memory":
            # MemoryResource role (/v1/memory): the REAL worker pool —
            # budget, total reserved, and per-query reservations from
            # task-admission static footprints (no fake 16GB heap)
            ps = self.tm.pool_stats()
            return _json_response(req, 200, {
                "pools": {"general": {
                    "maxBytes": ps["budgetBytes"] or (16 << 30),
                    "reservedBytes": ps["reservedBytes"],
                    "reservedRevocableBytes": ps["revokedBytes"],
                    "queryMemoryReservations": ps["queryReservations"],
                    "queryMemoryAllocations": {},
                    "queryMemoryRevocableReservations": {}}},
                "memoryPool": ps})
        return _json_response(req, 404, {"error": f"no route {path}"})

    def _spool_for(self, task_id: str):
        """Committed spool for a task no longer (or never) held live by
        this worker — ANY worker sharing the spool base can serve it."""
        spool = getattr(self.tm, "spool", None)
        if spool is None:
            return None
        return spool.find_committed_for_task(task_id)

    def _spool_results(self, req: Request, committed, buffer_id: str,
                       token: str) -> Response:
        """Serve GET .../results/... from a committed spool: the same
        headers and chunking as live buffers, tokens are frame indices
        from 0, instance id comes from the manifest (so a consumer that
        already pulled frames from the live task sees a CONSISTENT
        stream, not a WorkerRestartedError). Committed part files are
        immutable and frames sit back-to-back, so the range ships
        zero-copy via sendfile once it clears the size floor."""
        from presto_tpu.spool.store import record_fallback_read
        max_bytes = _parse_size(req.headers.get("X-Presto-Max-Size"),
                                16 << 20)
        tok = int(token)
        rng = committed.range_for(buffer_id, tok, max_bytes)
        if rng is None:
            # unknown buffer id in this manifest: same answer the live
            # path's exhausted buffer gives — empty and complete (the
            # pre-pool frames() behavior; a 404 here would surface as a
            # fatal response on a healthy recovery path)
            rng = ("", 0, 0, tok, True)
        path, offset, length, nxt, complete = rng
        record_fallback_read()
        headers = {
            "X-Presto-Task-Instance-Id": committed.instance_id,
            "X-Presto-Page-Sequence-Id": str(tok),
            "X-Presto-Page-End-Sequence-Id": str(nxt),
            "X-Presto-Buffer-Complete": "true" if complete else "false",
        }
        cfg = self.httpd.cfg if self.httpd is not None else DEFAULT_NET
        if length >= cfg.sendfile_min_bytes:
            return _pages_response(200, SendFile(path, offset, length),
                                   headers)
        if length == 0:
            return _pages_response(200, b"", headers)
        with open(path, "rb") as f:
            f.seek(offset)
            return _pages_response(200, f.read(length), headers)

    def _cold_results(self, req: Request, task_id: str, buffer_id: str,
                      token: str) -> Response:
        """Results GET for a task this worker no longer holds live:
        committed spool or 404."""
        committed = self._spool_for(task_id)
        if committed is not None:
            return self._spool_results(req, committed, buffer_id, token)
        return _json_response(req, 404, {"error": "no task/buffers"})

    def _closed_buffer_results(self, req: Request, task_id: str,
                               buffer_id: str, token: str) -> Response:
        """The task's buffers were closed under a long-poll (worker
        shutting down, task deleted, task FAILED under
        retry_policy=TASK): a committed spool serves the SAME bytes at
        the same tokens; otherwise refuse — never answer `complete` for
        frames this buffer no longer serves. A FAILED attempt's output
        is void and will not come back, so its consumers get what a
        deleted task's get (404: they fail and are re-planned against
        the replacement attempt) and the worker's breaker takes no
        penalty for a task's fault; a closing worker refuses
        retryably."""
        committed = self._spool_for(task_id)
        if committed is not None:
            return self._spool_results(req, committed, buffer_id, token)
        task = self.tm.get(task_id)
        if task is not None and task.state == "FAILED":
            return _json_response(
                req, 404, {"error": "task failed; its output is void"})
        return _json_response(
            req, 503, {"error": "output buffer closed (worker "
                       "shutting down); retry"})

    def _results(self, req: Request, task_id: str, buffer_id: str,
                 token: str) -> Response:
        task = self.tm.get(task_id)
        if task is None or task.buffers is None:
            return self._cold_results(req, task_id, buffer_id, token)
        mgr = task.buffers
        buf = mgr.buffer(buffer_id)
        if buf is None:
            return _json_response(req, 404, {"error": "no buffer"})
        max_bytes = _parse_size(req.headers.get("X-Presto-Max-Size"),
                                16 << 20)
        tok = int(token)
        # Long-poll until a page (or completion) is available; parked
        # waiters sleep on the buffer manager's Condition and wake
        # event-driven on page arrival / stream end / close.
        deadline = time.time() + _parse_duration(
            req.headers.get("X-Presto-Max-Wait"), 1.0)
        while True:
            seen = mgr.wake_version()
            try:
                frames, nxt, complete = buf.get(tok, max_bytes)
            except BufferClosedError:
                return self._closed_buffer_results(req, task_id,
                                                   buffer_id, token)
            remaining = deadline - time.time()
            if frames or complete or remaining <= 0:
                break
            mgr.wait_for_wake(seen, remaining)
        headers = {
            "X-Presto-Task-Instance-Id": str(task.instance_id),
            "X-Presto-Page-Sequence-Id": str(tok),
            "X-Presto-Page-End-Sequence-Id": str(nxt),
            "X-Presto-Buffer-Complete": "true" if complete else "false",
        }
        return _pages_response(200, frames, headers)

    # ----------------------------------------------------------- DELETE
    def _delete(self, req: Request) -> Response:
        path = req.path
        m = _REMOTE_SOURCE.match(path)
        if m:
            if not self.tm.remove_remote_source(m.group(1), m.group(2)):
                return _json_response(req, 404, {"error": "no task"})
            return _json_response(req, 200, {})
        m = _ABORT.match(path)
        if m:
            task = self.tm.get(m.group(1))
            if task is not None and task.buffers is not None:
                task.buffers.abort(m.group(2))
            return _json_response(req, 200, {})
        m = _TASK.match(path)
        if m:
            info = self.tm.delete(m.group(1))
            if info is None:
                return _json_response(req, 404, {"error": "no task"})
            return _json_response(req, 200, S.TaskInfo.to_json(info))
        return _json_response(req, 404, {"error": f"no route {path}"})


class TpuWorkerServer:
    """Bind + serve on the event loop; .port is assigned (0 = any)."""

    def __init__(self, connector, host: str = "127.0.0.1", port: int = 0,
                 coordinator_uri: Optional[str] = None,
                 node_id: str = "tpu-worker-0",
                 shared_secret: Optional[str] = None,
                 cache_config=None, spool_config=None,
                 exchange_config=None, elastic_config=None,
                 memory_config=None, net_config=None,
                 mesh_config=None):
        from presto_tpu.config import DEFAULT_ELASTIC
        self.elastic_config = (elastic_config
                               if elastic_config is not None
                               else DEFAULT_ELASTIC)
        self.app = WorkerApp()
        self.httpd = AioHttpServer(self.app, host, port, role="worker",
                                   net_config=net_config)
        self.port = self.httpd.port
        base = f"http://{host}:{self.port}"
        self.task_manager = TpuTaskManager(connector, base_uri=base,
                                           cache_config=cache_config,
                                           node_id=node_id,
                                           spool_config=spool_config,
                                           exchange_config=exchange_config,
                                           memory_config=memory_config,
                                           mesh_config=mesh_config)
        self.app.task_manager = self.task_manager
        self.app.httpd = self.httpd
        self.httpd.task_manager = self.task_manager
        # internal JWT auth (InternalAuthenticationManager role): with a
        # shared secret every /v1/* request must carry a valid
        # X-Presto-Internal-Bearer token; this node also SENDS signed
        # requests (announcements, exchange pulls)
        self.app.authenticator = None
        if shared_secret:
            from presto_tpu.server.auth import (
                InternalAuthenticator, configure,
            )
            self.app.authenticator = InternalAuthenticator(
                shared_secret, node_id)
            configure(shared_secret, node_id)
        self.httpd.authenticator = self.app.authenticator
        self.announcer = None
        if coordinator_uri:
            from presto_tpu.server.announcer import Announcer
            # the mesh slice rides the announcement payload so the
            # discovery surface shows it; a drained worker's next
            # round (or retraction) withdraws it
            self.announcer = Announcer(
                coordinator_uri, base, node_id,
                extra_properties=(
                    self.task_manager.mesh_tier.announce_properties))
        # back-reference for the PUT /v1/info/state handler: a drain
        # request must also retract the announcement once drained
        self.app.worker_server = self
        self.httpd.worker_server = self
        # always-on sampling profiler (GET /v1/profile); started from
        # the constructor, never from a request handler
        from presto_tpu.obs.profiler import PROFILER
        PROFILER.ensure_started()

    def start(self):
        self.httpd.start()
        if self.announcer:
            self.announcer.start()
        return self

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Graceful decommission: refuse new tasks, let running ones
        finish and commit spools, then retract the announcement so the
        coordinator drops this node from live membership immediately.
        The HTTP server keeps serving — already-produced pages and
        committed spools remain fetchable until stop()."""
        cfg = self.elastic_config
        report = self.task_manager.drain(
            timeout_s=cfg.drain_timeout_s if timeout_s is None
            else timeout_s,
            poll_s=cfg.drain_poll_s)
        if self.announcer:
            self.announcer.stop(retract=True)
        return report

    def stop(self):
        if self.announcer:
            # clean departure: halt the loop AND send the final
            # DELETE /v1/announcement/{nodeId} so the coordinator
            # learns immediately instead of waiting out staleness
            self.announcer.stop(retract=True)
        self.httpd.shutdown()
        self.httpd.server_close()
        self.task_manager.shutdown()
