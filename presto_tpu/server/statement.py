"""Client statement protocol: POST /v1/statement + nextUri polling.

Reference: QueuedStatementResource / ExecutingStatementResource
(presto-main/.../server/protocol/QueuedStatementResource.java:213,
ExecutingStatementResource.java) and the client contract in
presto-client/.../StatementClientV1.java:365 — a client POSTs SQL,
receives a QueryResults JSON with a `nextUri`, and polls it until
`nextUri` disappears; `columns` + `data` batches carry the rows, and
`stats.state` tracks QUEUED -> RUNNING -> FINISHED/FAILED.

This is the L0 surface over TpuCluster: accepted statements go through
the admission front door (`presto_tpu/admission/`) — shed check,
resource-group queueing, then a bounded dispatch pool executes them —
results buffer per query, and each GET serves one data batch.  The
HTTP handler never spawns execution threads itself."""

from __future__ import annotations

import asyncio
import json
import random
import re
import threading
import time as _time
import uuid
from typing import Callable, Dict, List, Optional

import presto_tpu.exec.dist_executor  # noqa: F401 — registers mesh metrics
from presto_tpu.admission import (DispatchManager, OverloadedError,
                                  QueryQueueFull, ResourceGroupManager)
from presto_tpu.admission import dispatcher as _dispatch
from presto_tpu.config import (DEFAULT_ADMISSION, DEFAULT_ELASTIC,
                               DEFAULT_OBS)
from presto_tpu.net.aio_server import AioHttpServer, Request, Response
from presto_tpu.server.journal import QueryJournal
from presto_tpu.obs.metrics import counter as _counter, gauge as _gauge
from presto_tpu.utils.threads import spawn
from presto_tpu.utils.tracing import TRACER, now, root_scope

_EXECUTING = re.compile(r"^/v1/statement/executing/([^/]+)/(\d+)$")
_QUEUED = re.compile(r"^/v1/statement/queued/([^/]+)/(\d+)$")
_CANCEL = re.compile(r"^/v1/statement/executing/([^/]+)$")
_TRACE = re.compile(r"^/v1/trace/([^/]+)$")
_INGEST = re.compile(r"^/v1/ingest/([^/]+)/([^/]+)/([^/]+)$")

_M_QUERIES = _counter("presto_tpu_coordinator_queries_total",
                      "Queries submitted to the coordinator, by outcome",
                      ("state",))
_M_COORD_UPTIME = _gauge(
    "presto_tpu_coordinator_uptime_seconds",
    "Seconds since this coordinator process started serving")
_M_ADOPTIONS = _counter(
    "presto_tpu_coordinator_ha_adoptions_total",
    "Journaled queries adopted from a dead peer coordinator under "
    "their original query id")

_COORD_START = _time.time()

_BATCH_ROWS = 4096


def _type_name(t) -> str:
    return str(t)


class _DoneEvent(threading.Event):
    """threading.Event plus completion callbacks: the async nextUri
    long-poll registers a loop-threadsafe waker here so a parked poll
    wakes the instant the query finishes instead of sleeping out its
    poll window. Callbacks fire exactly once, from whichever thread
    calls set(); one registered after set() fires immediately. `at` is
    the span clock when set() was called: the nextUri GET's
    `statement_results` span starts there, not at the parked poll."""

    def __init__(self):
        super().__init__()
        self._cb_lock = threading.Lock()
        self._cbs: List[Callable[[], None]] = []
        self.at: Optional[float] = None

    def add_callback(self, cb: Callable[[], None]) -> None:
        with self._cb_lock:
            if not self.is_set():
                self._cbs.append(cb)
                return
        cb()

    def remove_callback(self, cb: Callable[[], None]) -> None:
        with self._cb_lock:
            try:
                self._cbs.remove(cb)
            except ValueError:
                pass

    def set(self) -> None:
        self.at = now()
        super().set()
        with self._cb_lock:
            cbs, self._cbs = self._cbs, []
        for cb in cbs:
            try:
                cb()
            except Exception:   # noqa: BLE001 — a dead loop's waker
                pass            # must not break query completion


class _Query:
    def __init__(self, qid: str, sql: str, user: str = ""):
        self.qid = qid
        self.sql = sql
        self.user = user
        self.state = "QUEUED"
        self.dispatch_state: Optional[str] = None
        self.error: Optional[str] = None
        self.error_name = "GENERIC_INTERNAL_ERROR"
        self.error_type = "INTERNAL_ERROR"
        self.columns: Optional[List[dict]] = None
        self.rows: List[tuple] = []
        self.done = _DoneEvent()
        self.cancelled = False
        #: span clock at the hand-over to the dispatcher
        self.t_submitted: Optional[float] = None
        #: drawn once a statement, at submit: the protocol's spans are
        #: stored under the qid before `run` opens the trace
        self.sampled = DEFAULT_OBS.sampled(random.random())
        #: whether submit appended the statement to the journal
        self.journaled = False
        # final-batch cache: clients auto-retry nextUri GETs, so the
        # last data batch must survive serving it once — a replayed GET
        # of the same token re-serves the same rows instead of silently
        # returning FINISHED with no data
        self._final_token: Optional[int] = None
        self._final_batch: List = []
        # set once a terminal payload (final batch or error) has been
        # rendered to a client — only then is FIFO eviction safe; an
        # undelivered finished query evicted early 404s its owner's
        # next poll
        self.delivered = False

    def run(self, engine):
        """On the dispatcher's pool thread. The statement's trace opens
        here under the protocol query id; the wait it ends (submit ->
        admission grant -> a free pool thread: callback-driven, no
        thread sat in it) is recorded now, with its measured start."""
        self.state = "RUNNING"
        try:
            with root_scope(self.qid, self.sampled), \
                    TRACER.span(None, "statement", qid=self.qid) as sp:
                if self.t_submitted is not None:
                    handle = getattr(self, "_handle", None)
                    TRACER.record(
                        None, "admission_wait", self.t_submitted, now(),
                        parent_id=sp.span_id, mark=True,
                        group=getattr(handle, "group_path", None) or "")
                self._run(engine)
        finally:
            # after the statement span: what the nextUri GET does from
            # here on is the protocol's (`statement_results`)
            self.done.set()

    def _run(self, engine):
        try:
            rows = engine.execute_sql(self.sql)
            names = ()
            types = ()
            try:
                plan = engine.plan_sql(self.sql)
                names, types = plan.output_names, plan.output_types
            except Exception:   # noqa: BLE001 — DDL has no plan
                pass
            if not names:
                names = tuple(f"_col{i}"
                              for i in range(len(rows[0]) if rows else 1))
                types = ()
            self.columns = [
                {"name": n,
                 "type": _type_name(types[i]) if i < len(types)
                 else "unknown"}
                for i, n in enumerate(names)]
            # decimals travel as exact strings (the reference client
            # protocol's decimal encoding, presto-client QueryResults).
            # Keyed on the DECLARED column type, not the python value
            # shape, so scale-0 decimals (which materialize as ints)
            # encode identically to scaled ones.
            dec_cols = {i for i, t in enumerate(types)
                        if getattr(t, "is_decimal", False)}
            with TRACER.span(None, "collect_root", rows=len(rows)):
                self.rows = [
                    [None if v is None else
                     (str(v) if i in dec_cols
                      or type(v).__name__ == "Decimal" else v)
                     for i, v in enumerate(r)] for r in rows]
            self.state = "FINISHED"
        except Exception as e:  # noqa: BLE001 — rendered to the client
            self.error = f"{type(e).__name__}: {e}"[:500]
            if isinstance(e, QueryQueueFull):
                self.error_name = "QUERY_QUEUE_FULL"
                self.error_type = "INSUFFICIENT_RESOURCES"
            self.state = "FAILED"
        finally:
            if self.cancelled:
                # the engine call itself is not interruptible; report
                # the cancellation honestly instead of a silent FINISH
                self.state = "FAILED"
                self.error = "Query was canceled by the user"
                self.rows = []
            _M_QUERIES.inc(state=self.state)

    def results_json(self, base: str, token: int) -> dict:
        out = {
            "id": self.qid,
            "infoUri": f"{base}/v1/query/{self.qid}",
            "stats": {"state": self.state, "queued": self.state == "QUEUED",
                      "scheduled": self.state != "QUEUED"},
        }
        if self.state == "FAILED":
            out["error"] = {"message": self.error,
                            "errorName": self.error_name,
                            "errorType": self.error_type}
            self.delivered = True
            return out
        if self.state != "FINISHED":
            out["nextUri"] = \
                f"{base}/v1/statement/executing/{self.qid}/{token}"
            return out
        # FINISHED: serve data batches; nextUri until drained
        if self.columns is not None:
            out["columns"] = self.columns
        if self._final_token is not None:
            # already drained: the bulk buffer is released, but the
            # final batch stays cached so a client RETRY of the last
            # GET (response lost after the server built it) re-serves
            # the same rows — same-token GETs must be idempotent
            if token == self._final_token and self._final_batch:
                out["data"] = self._final_batch
            return out
        lo = token * _BATCH_ROWS
        hi = lo + _BATCH_ROWS
        batch = self.rows[lo:hi]
        if batch:
            out["data"] = batch
        if hi < len(self.rows):
            out["nextUri"] = \
                f"{base}/v1/statement/executing/{self.qid}/{token + 1}"
        else:
            # final batch served: release the buffered result (queries
            # stay listed for /v1/query info, rows do not accumulate)
            # but keep this batch for idempotent replay
            self._final_token = token
            self._final_batch = batch
            self.rows = []
            self.delivered = True
        return out


def _query_info(q) -> dict:
    """ONE query-info shape for the list and detail endpoints."""
    return {"queryId": q.qid, "state": q.state, "query": q.sql,
            "user": getattr(q, "user", ""),
            "dispatchState": getattr(q, "dispatch_state", None),
            "error": q.error}


class StatementApp:
    """The coordinator's request router, served by AioHttpServer. The
    two client hot paths — POST /v1/statement and the nextUri GET
    long-poll — run natively async (a parked poll is a coroutine
    waiting on the query's done event); every other route rides the
    loop's bounded executor via `handle`."""

    def __init__(self, coordinator: "StatementServer"):
        self.coordinator = coordinator

    @property
    def base(self) -> str:
        return self.coordinator.base

    def _dead(self, server) -> bool:
        """Crash-simulation check (StatementServer.kill): a killed
        coordinator's in-flight handlers must NOT answer — a dying
        process tears its connections, it does not serve one last
        response. A None response makes the server close the socket
        with no status line, which the client transport classifies as
        a connection error and fails over."""
        return bool(getattr(server, "dead", False))

    @staticmethod
    def _json(code: int, obj) -> Response:
        return Response(code, json.dumps(obj).encode())

    def _answer(self, q: "_Query", name: str, start: float, out: dict,
                **attributes) -> Response:
        """A statement-protocol response and its span: `name` covers
        `start` to now, when the response is built, as a back-dated
        marker (the handlers are coroutines on the loop, so no span can
        stay open around them); stored under the query id where the
        statement is sampled, annotated only where it is not. `bytes`
        is the response body's unless the caller says otherwise."""
        body = json.dumps(out).encode()
        attributes.setdefault("bytes", len(body))
        TRACER.record(q.qid if q.sampled else None, name, start, now(),
                      mark=True, **attributes)
        return Response(200, body)

    def _posted(self, q: "_Query", arrived: float, req: Request
                ) -> Response:
        """POST /v1/statement's answer: `statement_post` runs from the
        request's arrival through submit (admission, journal append,
        dispatch) to the first QueryResults."""
        return self._answer(q, "statement_post", arrived,
                            q.results_json(self.base, 0),
                            bytes=len(req.body), journal=q.journaled)

    def _results(self, q: "_Query", arrived: float, token: int
                 ) -> Response:
        """A nextUri GET's answer: `statement_results` starts at the
        later of the GET's arrival and the statement's end (the end of
        the park where it is still running), so the parked long-poll is
        left out and the loop's wake-up, `results_json` and the JSON
        encoding are in."""
        ready = q.done.at if q.done.is_set() else now()
        start = max(arrived, ready)
        out = q.results_json(self.base, token)
        return self._answer(q, "statement_results", start, out,
                            rows=len(out.get("data") or ()),
                            parked_ms=1e3 * (start - arrived))

    # -------------------------------------------------- async hot paths
    def dispatch_async(self, req: Request, server: AioHttpServer):
        if req.method == "POST" and req.path == "/v1/statement":
            return self._submit_async(server, req)
        if req.method == "GET":
            m = _EXECUTING.match(req.path) or _QUEUED.match(req.path)
            if m:
                return self._poll_async(server, req, m.group(1),
                                        int(m.group(2)))
            if req.path in ("/v1/metrics", "/v1/status", "/v1/alerts"):
                return self._snapshot_async(server, req)
        return None

    async def _snapshot_async(self, server: AioHttpServer,
                              req: Request):
        """Scrape-time computation (registry render, process gauges,
        admission/journal/alert snapshots) runs on the executor —
        never on the loop, where one slow scrape would stall every
        parked long-poll (tests/test_aio_server.py asserts this)."""
        if self._dead(server):
            return None
        return await server.run_blocking(self._get, req)

    async def _submit_async(self, server: AioHttpServer, req: Request):
        if self._dead(server):
            return None
        arrived = now()
        sql = req.body.decode()
        try:
            # admission + journal append touch locks and disk — run
            # them on the executor, never on the loop
            q = await server.run_blocking(
                self._do_submit, sql, req.headers.get(
                    "X-Presto-User", "") or "",
                req.headers.get("X-Presto-Source", "") or "",
                req.headers.get("X-Presto-Idempotency-Key"))
        except OverloadedError as e:
            return self._overloaded(e)
        return self._posted(q, arrived, req)

    def _do_submit(self, sql, user, source, idem) -> "_Query":
        return self.coordinator.submit(sql, user=user, source=source,
                                       idempotency_key=idem)

    def _overloaded(self, e: OverloadedError) -> Response:
        """Load shed: refuse at the door with the advised back-off; the
        transport layer treats 503 + Retry-After as its own retry class
        and sleeps exactly this interval."""
        body = json.dumps({"error": {
            "message": str(e),
            "errorName": "SERVER_OVERLOADED",
            "errorType": "INSUFFICIENT_RESOURCES",
            "retryAfterSeconds": e.retry_after_s}}).encode()
        return Response(503, body,
                        headers={"Retry-After": f"{e.retry_after_s:g}"})

    async def _poll_async(self, server: AioHttpServer, req: Request,
                          qid: str, token: int):
        if self._dead(server):
            return None
        arrived = now()
        co = self.coordinator
        q = co.queries.get(qid)
        if q is None:
            # multi-coordinator failover: a client re-resolving a dead
            # peer's nextUri here may be asking about a query this
            # coordinator never saw — adopt it from the shared journal
            # (disk I/O -> executor) under its ORIGINAL qid
            q = await server.run_blocking(co.adopt, qid)
        if q is None:
            return self._json(404, {"error": "no query"})
        # long-poll briefly while the query runs: park on the done
        # event's callback, zero threads held
        if not q.done.is_set():
            evt, wake = server.waiter()
            q.done.add_callback(wake)
            try:
                await asyncio.wait_for(evt.wait(), timeout=1.0)
            except asyncio.TimeoutError:
                pass
            finally:
                q.done.remove_callback(wake)
        if self._dead(server):   # killed mid-poll: die silently
            return None
        return self._results(q, arrived, token)

    # ------------------------------------------------------ sync router
    def handle(self, req: Request) -> Optional[Response]:
        server = self.coordinator.httpd
        if self._dead(server):
            return None
        if req.method == "POST":
            return self._post(req)
        if req.method == "GET":
            resp = self._get(req)
            if resp is None and self._dead(server):
                return None
            return resp
        if req.method == "DELETE":
            return self._delete(req)
        return self._json(404, {"error": "no route"})

    def _post(self, req: Request) -> Response:
        path = req.path
        m = _INGEST.match(path)
        if m:
            return self._do_ingest(req, *m.groups())
        if path != "/v1/statement":
            return self._json(404, {"error": "no route"})
        arrived = now()
        sql = req.body.decode()
        try:
            q = self.coordinator.submit(
                sql,
                user=req.headers.get("X-Presto-User", "") or "",
                source=req.headers.get("X-Presto-Source", "") or "",
                idempotency_key=req.headers.get(
                    "X-Presto-Idempotency-Key"))
        except OverloadedError as e:
            return self._overloaded(e)
        return self._posted(q, arrived, req)

    def _do_ingest(self, req: Request, catalog: str, schema: str,
                   table: str) -> Response:
        """Streaming-append batch: JSON ``{"rows": [[...], ...]}`` in,
        commit receipt (rows, post-append version, cumulative row
        count) out. The append itself is admitted through the ingest
        resource-group tenant inside IngestManager — the HTTP handler
        neither executes nor schedules anything itself."""
        from presto_tpu.stream.ingest import IngestError

        try:
            body = json.loads(req.body.decode() or "{}")
            rows = body["rows"]
            if not isinstance(rows, list):
                raise IngestError("'rows' must be a list of rows")
        except (ValueError, KeyError) as e:
            return self._json(400, {"error": f"bad ingest body: {e}"})
        try:
            receipt = self.coordinator.ingest(
                catalog, schema, table, rows)
        except IngestError as e:
            return self._json(400, {"error": str(e)})
        except QueryQueueFull as e:
            return self._json(429, {"error": str(e)})
        return self._json(200, receipt)

    def _get(self, req: Request) -> Optional[Response]:
        path = req.path
        m = _EXECUTING.match(path) or _QUEUED.match(path)
        if m:
            # threaded fallback for the nextUri poll (normally served
            # async): same adopt + bounded wait semantics
            arrived = now()
            q = self.coordinator.queries.get(m.group(1))
            if q is None:
                q = self.coordinator.adopt(m.group(1))
            if q is None:
                return self._json(404, {"error": "no query"})
            q.done.wait(timeout=1.0)
            if self._dead(self.coordinator.httpd):
                return None     # killed mid-poll: die silently
            return self._results(q, arrived, int(m.group(2)))
        if path == "/v1/query":
            # the query list (QueryResource.getAllQueryInfo role —
            # the UI's landing data)
            co = self.coordinator
            return self._json(200, [_query_info(q)
                                    for q in list(co.queries.values())])
        if path.startswith("/v1/query/"):
            q = self.coordinator.queries.get(path.rsplit("/", 1)[-1])
            if q is None:
                return self._json(404, {"error": "no query"})
            return self._json(200, _query_info(q))
        if path == "/v1/metrics":
            # same process-global registry the workers render — on the
            # coordinator a scrape additionally shows transport/breaker
            # counters for every worker host it talks to; process
            # gauges + scrape histogram via the shared scrape path
            from presto_tpu.obs.process import render_metrics_payload
            _M_COORD_UPTIME.set(_time.time() - _COORD_START)
            return Response(200, render_metrics_payload().encode(),
                            content_type="text/plain; version=0.0.4")
        if path == "/v1/alerts":
            # the alert engine's full state: every rule with its
            # current state machine position, plus the transition
            # history ring (matches system.runtime.alerts rows)
            eng = getattr(self.coordinator.engine, "alerts", None)
            if eng is None:
                return self._json(200, {"alerts": [],
                                        "transitions": []})
            return self._json(200, {"alerts": eng.snapshot(),
                                    "transitions": eng.transitions()})
        if path == "/v1/profile":
            # coordinator-side collapsed stacks (the profiler is
            # process-global, so in-process workers show here too)
            from presto_tpu.obs.profiler import PROFILER
            return Response(200, (PROFILER.collapsed() + "\n").encode(),
                            content_type="text/plain; charset=utf-8")
        if path == "/v1/ha/admission":
            # the peer-gossip surface: this coordinator's stride-WFQ
            # admission totals, polled by every peer's AdmissionGossip
            # so shedding/quotas act on cluster totals
            co = self.coordinator
            rgs = co.resource_groups
            return self._json(200, {
                "coordinatorId": co.coordinator_id,
                "queued": rgs.total_queued(),
                "running": rgs.total_running(),
                "draining": co.draining,
                "ts": _time.time()})
        if path == "/v1/status":
            # coordinator NodeStatus: uptime, role, query counts, and
            # the engine memory pool as the heap proxy
            co = self.coordinator
            qs = list(co.queries.values())
            eng = co.engine
            pool = getattr(eng, "memory_pool", None)
            rgs = co.resource_groups
            return self._json(200, {
                "nodeId": co.coordinator_id, "role": "coordinator",
                "environment": "tpu",
                "uptime": f"{_time.time() - _COORD_START:.2f}s",
                "uptimeSeconds": _time.time() - _COORD_START,
                "queryCount": len(qs),
                "runningQueries": sum(
                    1 for q in qs if not q.done.is_set()),
                "taskCount": 0,
                "heapUsed": pool.reserved if pool is not None else 0,
                "heapAvailable": 16 << 30, "nonHeapUsed": 0,
                # serving-tier snapshot: event-loop connection counts,
                # async vs executor route split, loop lag ticks
                "net": co.httpd.stats(),
                # per-group admission stats (reference:
                # ResourceGroupInfo on the cluster resource): live
                # queue depth / running plus lifetime counters per row
                "resourceGroups": (
                    {name: stats for name, stats in rgs.info()}
                    if rgs is not None else {}),
                # front-door snapshot: pool occupancy, queue-wait
                # percentiles, shed counters and thresholds
                "admission": co.dispatcher.snapshot(),
                # write-ahead journal state (None when crash recovery
                # is not configured) + the engine's membership view
                "journal": (co.journal.stats()
                            if co.journal is not None else None),
                "membership": (eng.membership_snapshot()
                               if hasattr(eng, "membership_snapshot")
                               else None),
                # multi-coordinator HA view: peers, drain state,
                # adoption count, and the gossip round snapshot
                "ha": {"coordinatorId": co.coordinator_id,
                       "peers": list(co.peers),
                       "draining": co.draining,
                       "adoptions": co.adoptions,
                       "gossip": (co.gossip.snapshot()
                                  if co.gossip is not None else None)},
                # alert-engine summary (full detail at /v1/alerts):
                # which rules are firing and every rule's state
                "alerts": self._alerts_block()})
        m = _TRACE.match(path)
        if m:
            # stitched cross-node span dump for one query id (worker
            # spans appear here after the cluster scraped them)
            return self._json(200, TRACER.to_json(m.group(1)))
        if path == "/v1/cluster":
            # ClusterStatsResource role: the cluster-overview numbers
            # the reference UI polls (running/queued/finished counts,
            # worker membership, memory reservation)
            co = self.coordinator
            qs = list(co.queries.values())
            queued = sum(1 for q in qs if q.state == "QUEUED")
            running = sum(1 for q in qs
                          if not q.done.is_set()
                          and q.state != "QUEUED")
            failed = sum(1 for q in qs
                         if q.done.is_set() and q.error is not None)
            finished = sum(1 for q in qs
                           if q.done.is_set() and q.error is None)
            eng = co.engine
            workers = list(getattr(eng, "worker_uris", []) or [])
            mem = 0
            pool = getattr(eng, "memory_pool", None)
            if pool is not None:
                mem = pool.reserved
            return self._json(200, {
                "runningQueries": running,
                "queuedQueries": queued,
                "finishedQueries": finished,
                "failedQueries": failed,
                "trackedQueries": len(qs),
                "activeWorkers": len(workers),
                "workers": workers,
                "reservedMemoryBytes": mem,
            })
        return self._json(404, {"error": f"no route {path}"})

    def _alerts_block(self) -> Optional[dict]:
        eng = getattr(self.coordinator.engine, "alerts", None)
        if eng is None:
            return None
        return {"firing": eng.firing(),
                "states": {a["rule"]: a["state"]
                           for a in eng.snapshot()}}

    def _delete(self, req: Request) -> Response:
        m = _CANCEL.match(req.path)
        if m:
            co = self.coordinator
            q = co.queries.get(m.group(1))
            if q is not None:
                q.cancelled = True
                co.cancel(q)
            return Response(204)         # no body with 204
        return self._json(404, {"error": "no route"})


class StatementServer:
    """The coordinator's client-facing HTTP surface over any engine with
    execute_sql/plan_sql (TpuCluster or LocalEngine).

    Multi-coordinator HA: N StatementServers run as symmetric peers
    over one shared ``QueryJournal`` file (pass the same
    ``elastic.journal_path`` and distinct ``coordinator_id``s, then
    wire the peer sets with :meth:`set_peers`). Every accepted
    statement is journaled with its owner; a peer that receives a
    nextUri poll for a query it never saw adopts it from the journal
    under the ORIGINAL qid (:meth:`adopt`), and peers gossip their
    stride-WFQ admission totals so shedding acts on cluster totals."""

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 0,
                 admission=None, resource_groups=None, elastic=None,
                 coordinator_id: str = "tpu-coordinator", peers=()):
        self.engine = engine
        self.coordinator_id = coordinator_id
        self.peers: List[str] = []
        self.draining = False
        self.adoptions = 0
        self.gossip = None
        self._started = False
        # coordinator crash recovery: with a journal path configured
        # (ElasticConfig.journal_path) every accepted statement is
        # write-ahead journaled and re-queued by recover() on restart
        self.elastic = elastic if elastic is not None else DEFAULT_ELASTIC
        self.journal = (QueryJournal(
            self.elastic.journal_path,
            compact_threshold=self.elastic.journal_compact_threshold)
            if self.elastic.journal_path else None)
        # share the engine's resource groups when it has them so the
        # front door and the engine agree on admission state (the
        # engine's own acquire becomes a no-op under the dispatcher)
        self.resource_groups = (resource_groups
                                or getattr(engine, "resource_groups",
                                           None)
                                or ResourceGroupManager())
        self.admission_config = admission or DEFAULT_ADMISSION
        self.dispatcher = DispatchManager(
            self.resource_groups, self.admission_config,
            memory_pool=getattr(engine, "memory_pool", None))
        # observability time-dimension wiring (engines without a
        # telemetry plane — LocalEngine — skip both): the shedder
        # reads the cluster-wide windowed queue-wait p99 from the
        # telemetry history instead of its private sliding window,
        # and the journal append-age gauge refreshes on every scrape
        # so the JournalAppendStalled alert evaluates a live value
        telemetry = getattr(engine, "telemetry", None)
        if telemetry is not None:
            self.dispatcher.shedder.attach_history(
                lambda: telemetry.windowed_quantile(
                    "presto_tpu_admission_queue_wait_seconds"))
            if self.journal is not None:
                telemetry.add_refresher(
                    lambda: self.journal.stats())
        self.queries: Dict[str, _Query] = {}
        # client idempotency key -> qid: POST /v1/statement is
        # auto-retried by the transport, and a retry after a LOST
        # response must attach to the already-running query instead of
        # re-executing the SQL (an INSERT/CTAS replay would silently
        # duplicate rows)
        self._idempotency: Dict[str, str] = {}
        self._submit_lock = threading.Lock()
        # the front door: asyncio event loop + bounded executor (see
        # presto_tpu/net/aio_server.py) — POST /v1/statement and the
        # nextUri long-poll are async-native, everything else dispatches
        # through the executor. Port is bound in the ctor.
        self.app = StatementApp(self)
        self.httpd = AioHttpServer(self.app, host, port,
                                   role="coordinator")
        self.httpd.coordinator = self
        self.port = self.httpd.port
        self.base = f"http://{host}:{self.port}"
        self.httpd.base = self.base
        self._thread = spawn("coordinator", "statement-http",
                             self.httpd.serve_forever, start=False)
        # introspection plane: the system connector unions this front
        # door's live dispatcher states into system.runtime.queries via
        # this back-reference; the wide-event sink and profiler start
        # here too so a statement-only deployment still gets both.
        # With multiple peer coordinators over one engine every
        # instance also registers in statement_frontends, so
        # system.runtime.nodes can list coordinator rows per peer.
        setattr(engine, "statement_frontend", self)
        fronts = getattr(engine, "statement_frontends", None)
        if fronts is None:
            fronts = []
            setattr(engine, "statement_frontends", fronts)
        fronts.append(self)
        if peers:
            self.set_peers(peers)
        from presto_tpu.obs.profiler import PROFILER
        from presto_tpu.obs.wide_events import install_event_log_sink
        install_event_log_sink()
        PROFILER.ensure_started()

    def set_peers(self, peers) -> None:
        """Declare the peer coordinator set (base URIs; this server's
        own base is filtered out, so the full fleet list can be passed
        symmetrically to every member). Rewires the admission gossip
        and points the LoadShedder's queue-depth signal at cluster
        totals."""
        from presto_tpu.server.ha import AdmissionGossip
        self.peers = [p.rstrip("/") for p in peers
                      if p.rstrip("/") != self.base]
        if self.gossip is not None:
            self.gossip.stop()
            self.gossip = None
        if self.peers:
            self.gossip = AdmissionGossip(
                self.coordinator_id, self.resource_groups, self.peers)
            self.dispatcher.shedder.cluster_queued = \
                self.gossip.cluster_queued
            if self._started:
                self.gossip.start()
        else:
            self.dispatcher.shedder.cluster_queued = None

    #: completed queries kept for /v1/query info (QueryTracker role)
    MAX_TRACKED = 200

    def submit(self, sql: str, user: str = "", source: str = "",
               idempotency_key: Optional[str] = None) -> _Query:
        with self._submit_lock:
            if idempotency_key is not None:
                known = self._idempotency.get(idempotency_key)
                dup = self.queries.get(known) if known else None
                if dup is not None:
                    return dup          # retried POST: do NOT re-execute
            if self.draining:
                # graceful shutdown: refuse new work with the standard
                # 503 + Retry-After so the client's failover loop moves
                # to a peer coordinator instead of erroring out
                raise OverloadedError(
                    "coordinator draining",
                    self.admission_config.retry_after_s)
            # shed BEFORE registering: a refused statement must leave
            # no trace (the client retries with the same idempotency
            # key and must get a fresh admission decision)
            self.dispatcher.shedder.check()
            qid = f"{uuid.uuid4().hex[:16]}"
            q = _Query(qid, sql, user=user)
            self.queries[qid] = q
            if idempotency_key is not None:
                self._idempotency[idempotency_key] = qid
            if len(self.queries) > self.MAX_TRACKED:
                # FIFO-evict finished queries (dict preserves insertion
                # order), and drop idempotency entries with them.
                # Delivered queries go first: evicting a finished query
                # whose owner hasn't fetched the final batch yet 404s
                # its next poll — under a 1000-client storm that's a
                # dropped query. Undelivered ones are only reclaimed
                # past a 10x hard cap (memory bound beats the SLO only
                # when the registry is genuinely blowing up).
                for old_id in list(self.queries):
                    if len(self.queries) <= self.MAX_TRACKED:
                        break
                    old = self.queries[old_id]
                    if old.done.is_set() and old.delivered:
                        del self.queries[old_id]
                hard_cap = self.MAX_TRACKED * 10
                if len(self.queries) > hard_cap:
                    for old_id in list(self.queries):
                        if len(self.queries) <= hard_cap:
                            break
                        if self.queries[old_id].done.is_set():
                            del self.queries[old_id]
                self._idempotency = {
                    k: v for k, v in self._idempotency.items()
                    if v in self.queries}
        # write-ahead: journal the statement BEFORE dispatch so a
        # coordinator crash between admission and completion leaves a
        # recoverable record (group path is advisory — selection is
        # deterministic on (user, source), so recovery re-selects it)
        if self.journal is not None:
            self.journal.append(qid, sql=sql, user=user, source=source,
                                group=self._group_path(user, source),
                                state="QUEUED",
                                owner=self.coordinator_id)
            q.journaled = True
        try:
            self._dispatch(q, user=user, source=source)
        except OverloadedError:
            with self._submit_lock:
                self.queries.pop(qid, None)
                if idempotency_key is not None:
                    self._idempotency.pop(idempotency_key, None)
            raise
        return q

    def ingest(self, catalog: str, schema: str, table: str,
               rows) -> dict:
        """POST /v1/ingest/{catalog}/{schema}/{table} backend: one
        shared IngestManager per engine (lazy; tenant group + counters
        live there)."""
        from presto_tpu.stream.ingest import ingest_manager
        return ingest_manager(self.engine).append(
            catalog, schema, table, rows)

    def _group_path(self, user: str, source: str) -> Optional[str]:
        try:
            return self.resource_groups.select(
                user=user, source=source).path
        except Exception:   # noqa: BLE001 — the path is advisory
            return None

    def _dispatch(self, q: _Query, user: str, source: str) -> None:
        """Route one registered _Query through the admission
        dispatcher, with journal appends on every lifecycle transition.
        Raises OverloadedError (shed); queue-full failures close the
        query cleanly instead."""

        def _on_state(state: str, error) -> None:
            q.dispatch_state = state
            if state == _dispatch.FAILED and error is not None \
                    and not q.done.is_set():
                # rejected before execution (queue full, queue-timeout
                # eviction, cancelled while queued): q.run never ran,
                # so close the protocol query here
                q.error = f"{type(error).__name__}: {error}"[:500]
                if isinstance(error, QueryQueueFull):
                    q.error_name = "QUERY_QUEUE_FULL"
                    q.error_type = "INSUFFICIENT_RESOURCES"
                q.state = "FAILED"
                _M_QUERIES.inc(state="FAILED")
                q.done.set()
                if self.journal is not None:
                    self.journal.append(q.qid, state="FAILED")

        def _run() -> None:
            if self.journal is not None:
                self.journal.append(q.qid, state="RUNNING")
            q.run(self.engine)
            if self.journal is not None:
                self.journal.append(q.qid, state=q.state)

        q.t_submitted = now()
        try:
            q._handle = self.dispatcher.submit(
                _run, user=user, source=source,
                query_id=q.qid, listener=_on_state)
        except OverloadedError:
            raise
        except QueryQueueFull as e:
            _on_state(_dispatch.FAILED, e)      # clean rejection

    def recover(self) -> int:
        """Coordinator crash recovery: re-queue every journaled
        non-terminal query from a previous coordinator process through
        the admission front door, under the ORIGINAL query ids so
        clients polling pre-crash nextUris re-attach. QUEUED queries
        re-dispatch exactly like fresh submissions; RUNNING ones re-run
        — under ``retry_policy=TASK`` the re-execution absorbs any
        spools the previous run committed instead of redoing that work.
        Returns the number of queries re-queued."""
        if self.journal is None:
            return 0
        grace = float(getattr(self.elastic, "recover_grace_s", 0) or 0)
        if grace > 0:
            _time.sleep(grace)
        n = 0
        for rec in self.journal.pending():
            qid, sql = rec.get("qid"), rec.get("sql")
            if not qid or not sql or qid in self.queries:
                continue
            # a shared journal holds every peer's records: a restart
            # only re-queues its OWN (ownerless legacy records too);
            # a live peer's in-flight queries are not ours to re-run
            if rec.get("owner") not in (None, self.coordinator_id):
                continue
            user = rec.get("user", "") or ""
            requeues = int(rec.get("recoveries", 0) or 0)
            cap = int(getattr(self.elastic, "recover_max_requeues", 3))
            if requeues >= cap:
                # repeated crashes keep orphaning this query; abandon
                # it with a terminal record instead of letting an
                # unbounded recovery storm clog the admission queue
                q = _Query(qid, sql, user=user)
                q.error = (f"abandoned after {requeues} crash-recovery "
                           f"re-queues")
                q.state = "FAILED"
                q.done.set()
                with self._submit_lock:
                    self.queries[qid] = q
                self.journal.append(qid, state="FAILED",
                                    owner=self.coordinator_id)
                continue
            q = _Query(qid, sql, user=user)
            with self._submit_lock:
                self.queries[qid] = q
            self.journal.append(qid, state="QUEUED",
                                owner=self.coordinator_id,
                                recoveries=requeues + 1)
            try:
                self._dispatch(q, user=user,
                               source=rec.get("source", "") or "")
            except OverloadedError as e:
                # recovery never sheds silently: close the query with
                # the rejection so the journal reaches a terminal state
                q.error = f"{type(e).__name__}: {e}"[:500]
                q.state = "FAILED"
                q.done.set()
                self.journal.append(qid, state="FAILED")
                continue
            self.journal.mark_recovered()
            n += 1
        return n

    def adopt(self, qid: str) -> Optional[_Query]:
        """Multi-coordinator failover: take over a dead peer's
        journaled query under its ORIGINAL qid. Called when a client's
        nextUri poll lands here for a query this coordinator never
        registered — refresh the shared journal from disk (the peer's
        appends were never in our memory view), and if the record is
        live, re-queue it through our own admission front door.

        Terminal records are adoptable too: results live only in the
        owner's memory, so a query that FINISHED just before its owner
        died — with the client's poll still in flight — must be re-run
        here or the client can never fetch it. That re-execution is
        safe because adoption only triggers from an unanswered poll
        (the results were never delivered) and this statement surface
        is read-only analytics; a journaled FAILED query deterministic-
        ally re-delivers its error. Returns None when there is nothing
        adoptable (no journal, unknown qid, no recorded sql, or we are
        draining)."""
        if self.journal is None or self.draining:
            return None
        self.journal.refresh()
        rec = self.journal.get(qid)
        if rec is None or not rec.get("sql"):
            return None
        user = rec.get("user", "") or ""
        with self._submit_lock:
            dup = self.queries.get(qid)
            if dup is not None:
                return dup      # raced with another poll: one adoption
            q = _Query(qid, rec["sql"], user=user)
            self.queries[qid] = q
        # adoption is never capped (a live client is polling this qid)
        # but still counts toward the crash-recovery re-queue budget an
        # UNATTENDED restart honors in recover()
        self.journal.append(qid, state="QUEUED",
                            owner=self.coordinator_id,
                            recoveries=int(rec.get("recoveries", 0)
                                           or 0) + 1)
        try:
            self._dispatch(q, user=user,
                           source=rec.get("source", "") or "")
        except OverloadedError as e:
            # adoption never sheds silently — the client is already
            # polling this qid, so close it with the rejection
            q.error = f"{type(e).__name__}: {e}"[:500]
            q.state = "FAILED"
            q.done.set()
            self.journal.append(qid, state="FAILED")
            return q
        self.adoptions += 1
        _M_ADOPTIONS.inc()
        self.journal.mark_recovered()
        return q

    def cancel(self, q: _Query) -> bool:
        """Withdraw a statement still waiting for admission; running
        queries are only flagged (the engine call is uninterruptible,
        `_Query.run` reports the cancellation when it returns)."""
        h = getattr(q, "_handle", None)
        return h is not None and self.dispatcher.cancel(h)

    def start(self) -> "StatementServer":
        self._thread.start()
        self._started = True
        # crash recovery before the first client request lands: any
        # journaled non-terminal queries from a previous process are
        # back in the admission queue by the time start() returns
        if self.journal is not None:
            self.recover()
        if self.gossip is not None:
            self.gossip.start()
        return self

    def stop(self, drain_timeout_s: Optional[float] = None):
        """Graceful coordinator shutdown: stop accepting (draining
        submits shed with Retry-After so clients fail over), then
        bounded-wait for in-flight dispatch-pool queries to finish —
        the same drain discipline as the PR 10 worker drain — so a
        deliberately stopped coordinator journals/finishes what it can
        instead of abandoning in-flight queries."""
        self.draining = True
        timeout = (drain_timeout_s if drain_timeout_s is not None
                   else float(getattr(self.elastic, "drain_timeout_s",
                                      0) or 0))
        poll = float(getattr(self.elastic, "drain_poll_s", 0.05)
                     or 0.05)
        deadline = _time.time() + timeout
        while _time.time() < deadline:
            with self._submit_lock:
                inflight = [q for q in self.queries.values()
                            if not q.done.is_set()]
            if not inflight:
                break
            _time.sleep(poll)
        if self.gossip is not None:
            self.gossip.stop()
        if self._thread.is_alive():     # shutdown() blocks forever
            self.httpd.shutdown()       # unless serve_forever runs
        self.httpd.server_close()
        self.dispatcher.stop()
        # deliberate decommission leaves the fleet registry; a KILLED
        # coordinator stays registered so system.runtime.nodes shows
        # the DEAD row
        fronts = getattr(self.engine, "statement_frontends", None)
        if fronts is not None:
            try:
                fronts.remove(self)
            except ValueError:
                pass

    def kill(self):
        """Crash simulation for chaos tests: no drain, no terminal
        journal appends. The journal handle is dropped FIRST so any
        still-running dispatch threads of this \"dead\" process cannot
        journal their outcomes — exactly the window a real crash
        leaves, which a surviving peer must repair by adoption."""
        self.draining = True
        self.journal = None
        # in-flight handler threads check this and tear their
        # connections instead of serving one last response
        self.httpd.dead = True
        if self.gossip is not None:
            self.gossip.stop()
        if self._thread.is_alive():
            self.httpd.shutdown()
        self.httpd.server_close()
        self.dispatcher.stop(timeout_s=0.0)


def run_statement(base_uri: str, sql: str, timeout_s: float = 600,
                  user: str = ""):
    """Client side of the protocol (StatementClientV1.advance loop):
    POST, then follow nextUri until it disappears; returns
    (columns, rows). Raises on FAILED."""
    import time

    from presto_tpu.protocol.transport import get_client

    client = get_client()
    # per-execute idempotency key: the transport auto-retries the POST,
    # and the server dedupes on the key so a retry after a lost
    # response attaches to the in-flight query instead of re-running
    # the SQL (which would duplicate INSERT/CTAS writes)
    headers = {"Content-Type": "text/plain",
               "X-Presto-Idempotency-Key": uuid.uuid4().hex}
    if user:
        headers["X-Presto-User"] = user
    payload = client.post(f"{base_uri}/v1/statement", sql.encode(),
                          headers=headers,
                          request_class="statement").json()
    columns, rows = None, []
    deadline = time.time() + timeout_s
    while True:
        if "error" in payload:
            raise RuntimeError(payload["error"]["message"])
        if payload.get("columns"):
            columns = payload["columns"]
        rows.extend(payload.get("data", []))
        nxt = payload.get("nextUri")
        if not nxt:
            return columns, rows
        if time.time() > deadline:
            raise TimeoutError(f"query {payload.get('id')} timed out")
        payload = client.get_json(nxt, request_class="statement")
