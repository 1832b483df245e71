"""Task manager: TaskUpdateRequest -> translated fragment -> executed
pages in output buffers, with TaskInfo/TaskStatus state tracking.

Reference roles: presto_cpp/main/TaskManager.cpp:506,544,580 (create or
update task, add splits, wire output buffers, resolve long-poll promises)
and execution/SqlTaskManager.java:393. The engine difference is
deliberate: instead of incremental drivers, the whole fragment executes as
one jit program per split batch (exec/executor.py), then results stream
through the token/ack buffer protocol unchanged."""

from __future__ import annotations

import dataclasses
import threading
import time
import traceback
import uuid
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from presto_tpu.data.column import (
    Page, concat_pages_host, device_leaves, page_nbytes, page_to_host,
    select_page_host,
)
from presto_tpu.exec.split_executor import SplitExecutor
from presto_tpu.obs.metrics import (
    counter as _counter, gauge as _gauge, histogram as _histogram,
)
from presto_tpu.plan.nodes import RemoteSourceNode
from presto_tpu.protocol import structs as S
from presto_tpu.protocol.serde import (
    encode_serialized_page, note_exchange_pages, page_to_wire_blocks,
)
from presto_tpu.server.buffers import OutputBufferManager
from presto_tpu.utils.threads import spawn
from presto_tpu.utils.tracing import TRACER, TraceContext, trace_scope

_M_TASKS_CREATED = _counter("presto_tpu_tasks_created_total",
                            "Tasks ever created on this worker")
_M_TASK_TRANSITIONS = _counter(
    "presto_tpu_task_state_transitions_total",
    "Task state transitions by destination state", ("state",))
_M_TASKS_BY_STATE = _gauge(
    "presto_tpu_worker_tasks",
    "Live tasks currently held by the task manager, by state",
    ("state",))
_M_PENDING_SPLITS = _gauge(
    "presto_tpu_worker_pending_splits",
    "Splits received but not yet bound to a scan across live tasks")
_M_OUTPUT_BYTES = _gauge(
    "presto_tpu_worker_output_bytes",
    "Bytes currently buffered in live tasks' output buffers")
_M_TASKS_LIVE = _gauge("presto_tpu_tasks",
                       "Live tasks currently held by the task manager")
_M_LIFETIME_BYTES = _gauge(
    "presto_tpu_task_bytes_out",
    "Lifetime bytes emitted into output buffers (survives task delete)")
_M_DF_PRUNED = _counter(
    "presto_tpu_dynamic_filter_rows_pruned_total",
    "Probe-side scan rows skipped by cross-exchange dynamic filters")
_M_DRAIN_SECONDS = _histogram(
    "presto_tpu_worker_drain_seconds",
    "Wall seconds a graceful decommission spent waiting for running "
    "tasks to finish")
_M_DRAIN_REJECTS = _counter(
    "presto_tpu_worker_drain_rejected_tasks_total",
    "Task creations refused because this worker was SHUTTING_DOWN")

#: task states the by-state gauge always reports (zeros included, so a
#: scrape sees a stable series set)
_TASK_STATES = ("PLANNED", "RUNNING", "FINISHED", "FAILED", "ABORTED")



def _scan_tables(frag: S.PlanFragment) -> Dict[str, str]:
    """planNodeId -> table name for every scan in the fragment (reference:
    PrestoToVeloxSplit binding splits to their scan nodes)."""
    out: Dict[str, str] = {}

    def walk(n):
        if isinstance(n, S.TableScanNode):
            h = n.table or {}
            ch = h.get("connectorHandle", {})
            t = ch.get("tableName") or ch.get("table")
            if t:
                out[n.id] = t
        for attr in ("source", "left", "right", "filteringSource"):
            c = getattr(n, attr, None)
            if c is not None and not isinstance(c, (str, dict, list)):
                walk(c)
    walk(frag.root)
    return out


def _fragment_has_remote_sources(frag: S.PlanFragment) -> bool:
    """Does the protocol fragment contain any RemoteSourceNode (it then
    needs remote splits before starting)?"""
    found = [False]

    def walk(n):
        if isinstance(n, S.RemoteSourceNode):
            found[0] = True
        if isinstance(n, S.RawNode):
            return
        for py, _js, codec in type(n)._SCHEMA:
            v = getattr(n, py)
            if v is None:
                continue
            if codec is S.PlanNode:
                walk(v)
            elif isinstance(codec, tuple) and len(codec) == 2 \
                    and codec[1] is S.PlanNode and isinstance(v, list):
                for c in v:
                    walk(c)
    walk(frag.root)
    return found[0]


def _concat_upload(pages: List[Page], source: str) -> Page:
    """Pulled pages fused row-wise in numpy and put on the device as one
    input page: the host->device step of an exchange, and the only one
    (the pulled pages are host pages). `bytes` is the fused page at its
    capacity; `device_puts` its arrays, `device_fetches` the arrays of
    the pulled pages that had to come back from the device first (none
    from `decode_pages`)."""
    with TRACER.span(None, "upload", source=source) as sp:
        fetches = note_exchange_pages("fuse", pages)
        page = concat_pages_host(pages)
        sp.attributes.update(bytes=page_nbytes(page),
                             device_fetches=fetches,
                             device_puts=device_leaves(page))
    return page


def _fragment_of(task_id: str) -> str:
    """The fragment id inside a task id `<query>.<fragment>.<...>`."""
    parts = task_id.split(".")
    return parts[1] if len(parts) > 1 else ""


def _remote_source_nodes(plan) -> List[RemoteSourceNode]:
    """Engine-plan walk: every RemoteSourceNode (pull inputs)."""
    out: List[RemoteSourceNode] = []

    def walk(n):
        if isinstance(n, RemoteSourceNode):
            out.append(n)
        for c in n.children():
            walk(c)
    walk(plan)
    return out


def _hash_partition_ids(page: Page, channels: Tuple[int, ...],
                        nbuf: int) -> np.ndarray:
    """Host-side row -> destination partition. Any hash works as long as
    every producer task of a stage agrees (reference:
    operator/InterpretedHashGenerator.java — consistency matters, the
    exact function only matters for bucketed-table interop). Strings hash
    their *bytes* (crc32), not dictionary codes — codes are per-task."""
    n = int(page.num_rows)
    acc = np.zeros(n, np.uint64)
    mult = np.uint64(0x9E3779B97F4A7C15)
    for ch in channels:
        c = page.columns[ch]
        v, nl = c.to_numpy(n)
        if c.type.is_string and c.dictionary is not None:
            words = c.dictionary.words
            wh = np.array([zlib.crc32(w.encode()) for w in words]
                          or [0], dtype=np.uint64)
            h = wh[np.clip(v, 0, len(wh) - 1)]
        elif v.dtype.kind == "f":
            # canonicalize like ops/keys.group_values so SQL-equal floats
            # hash equal across producers (-0.0 == 0.0; one NaN class)
            vf = np.asarray(v, dtype=np.float64).copy()
            vf[vf == 0.0] = 0.0
            vf[np.isnan(vf)] = np.nan
            h = vf.view(np.uint64).copy()
        elif v.dtype.kind == "b":
            h = v.astype(np.uint64)
        else:
            h = v.astype(np.int64).view(np.uint64)
        h = np.where(nl, np.uint64(0), h)
        acc = acc * mult + h
    # splittable-mix finish so low-entropy keys spread
    acc ^= acc >> np.uint64(33)
    acc *= np.uint64(0xFF51AFD7ED558CCD)
    acc ^= acc >> np.uint64(33)
    return (acc % np.uint64(max(nbuf, 1))).astype(np.int64)


class WorkerDrainingError(RuntimeError):
    """A task creation arrived while this worker was SHUTTING_DOWN.
    The HTTP layer maps this to 410 + X-Presto-Draining so the
    coordinator reschedules elsewhere without a breaker penalty."""


class Task:
    def __init__(self, task_id: str):
        self.task_id = task_id
        self.instance_id = uuid.uuid4()
        self.state = "PLANNED"
        self.created = time.time()
        self.version = 1
        self.failures: List[str] = []
        self.buffers: Optional[OutputBufferManager] = None
        self.fragment: Optional[S.PlanFragment] = None
        self.splits: Dict[str, List[Tuple[int, int]]] = {}
        # planNodeId -> [(upstream task uri, buffer id)] (RemoteSplit role:
        # presto-main-base/.../split/RemoteSplit.java — location + token)
        self.remote_splits: Dict[str, List[Tuple[str, str]]] = {}
        self.scan_tables: Dict[str, str] = {}
        self.seen_splits: set = set()
        self.pending_splits: List[S.ScheduledSplit] = []
        self.no_more_splits = False
        self.session_properties: Dict[str, str] = {}
        self.update_lock = threading.Lock()
        self.state_change = threading.Condition()
        self.bytes_out = 0
        # execution stats (TaskStats/OperatorStats roles)
        self.start_time: Optional[float] = None
        self.end_time: Optional[float] = None
        self.cpu_nanos = 0
        self.memory_bytes = 0
        self.raw_input_positions = 0
        self.output_positions = 0
        self.operator_stats: List[dict] = []   # per-plan-node summaries
        self.total_splits = 0
        # fragment result cache observability (FragmentCacheStats role):
        # was THIS task served from cache, plus a worker-store counter
        # snapshot taken when the task settles
        self.cache_hit = False
        self.cache_stats: dict = {}
        # when set to a list, _emit_output also records the pre-
        # partitioning pages for the populate step
        self._cache_pages: Optional[list] = None
        # cross-exchange dynamic filtering (reference:
        # DynamicFilterSourceOperator feeding the coordinator's
        # DynamicFilterService): a build task summarizes one output
        # channel's key domain; a probe task applies coordinator-pushed
        # scan constraints before executing
        self.df_channel: Optional[int] = None
        self.df_domain: Optional[dict] = None
        self.scan_constraints: Dict[str, dict] = {}
        self.df_pruned = 0
        self._df_nodes: List[tuple] = []
        # propagated X-Presto-Trace context (query trace id + the
        # coordinator-side parent span) — None when the query is
        # unsampled or the coordinator predates tracing
        self.trace_ctx: Optional[TraceContext] = None

    def set_state(self, state: str):
        with self.state_change:
            self.state = state
            self.version += 1
            self.state_change.notify_all()
        _M_TASK_TRANSITIONS.inc(state=state)

    # ---- protocol views -------------------------------------------------
    def status(self, base_uri: str = "") -> S.TaskStatus:
        running = 1 if self.state == "RUNNING" else 0
        return S.TaskStatus(
            taskInstanceIdLeastSignificantBits=(
                self.instance_id.int & ((1 << 64) - 1)),
            taskInstanceIdMostSignificantBits=self.instance_id.int >> 64,
            version=self.version,
            state=self.state,
            self_uri=f"{base_uri}/v1/task/{self.task_id}",
            queuedPartitionedDrivers=(
                1 if self.state == "PLANNED" else 0),
            runningPartitionedDrivers=running,
            runningPartitionedSplitsWeight=running,
            physicalWrittenDataSizeInBytes=self.bytes_out,
            memoryReservationInBytes=self.memory_bytes,
            peakNodeTotalMemoryReservationInBytes=self.memory_bytes,
            totalCpuTimeInNanos=self.cpu_nanos,
            taskAgeInMillis=int((time.time() - self.created) * 1000),
            failures=[{"message": m, "type": "PRESTO_TPU"}
                      for m in self.failures],
        )

    def stats_tree(self) -> dict:
        """TaskStats JSON (shape-compatible subset of the reference's
        presto_cpp/main/tests/data/TaskInfo.json stats; the pipeline's
        operatorSummaries carry per-plan-node rows)."""
        now = time.time()
        end = self.end_time or now
        start = self.start_time or self.created
        done = self.state in ("FINISHED", "FAILED", "ABORTED",
                              "CANCELED")
        df_domains = {}
        if done and self.state == "FINISHED" \
                and self.df_channel is not None \
                and self.df_domain is not None:
            d = dict(self.df_domain)
            vals = d.get("values")
            d["values"] = (sorted(vals) if isinstance(vals, set)
                           else None)
            df_domains = {str(self.df_channel): d}
        return {
            "createTimeInMillis": int(self.created * 1000),
            "firstStartTimeInMillis": int(start * 1000),
            "lastStartTimeInMillis": int(start * 1000),
            "lastEndTimeInMillis": int(end * 1000),
            "endTimeInMillis": int(end * 1000) if done else 0,
            "elapsedTimeInNanos": int((end - self.created) * 1e9),
            "queuedTimeInNanos": int((start - self.created) * 1e9),
            "totalDrivers": 1,
            "queuedDrivers": 1 if self.state == "PLANNED" else 0,
            "runningDrivers": 1 if self.state == "RUNNING" else 0,
            "completedDrivers": 1 if done else 0,
            "blockedDrivers": 0,
            "blockedReasons": [],
            "fullyBlocked": False,
            "totalSplits": self.total_splits,
            "queuedSplits": 0,
            "runningSplits": 0,
            "completedSplits": self.total_splits if done else 0,
            "cumulativeUserMemory": float(self.memory_bytes),
            "cumulativeTotalMemory": float(self.memory_bytes),
            "userMemoryReservationInBytes": self.memory_bytes,
            "systemMemoryReservationInBytes": 0,
            "revocableMemoryReservationInBytes": 0,
            "peakUserMemoryInBytes": self.memory_bytes,
            "peakTotalMemoryInBytes": self.memory_bytes,
            "peakNodeTotalMemoryInBytes": self.memory_bytes,
            "totalScheduledTimeInNanos": self.cpu_nanos,
            "totalCpuTimeInNanos": self.cpu_nanos,
            "totalBlockedTimeInNanos": 0,
            "totalAllocationInBytes": self.memory_bytes,
            "rawInputDataSizeInBytes": 0,
            "rawInputPositions": self.raw_input_positions,
            "processedInputDataSizeInBytes": 0,
            "processedInputPositions": self.raw_input_positions,
            "outputDataSizeInBytes": self.bytes_out,
            "outputPositions": self.output_positions,
            "physicalWrittenDataSizeInBytes": self.bytes_out,
            "fullGcCount": 0,
            "fullGcTimeInMillis": 0,
            # build-side key domains, published only once the task is
            # FINISHED so a consumer never applies a partial domain
            "dynamicFilterDomains": df_domains,
            "runtimeStats": self._runtime_stats(),
            "pipelines": ([{
                "pipelineId": 0,
                "firstStartTimeInMillis": int(start * 1000),
                "lastStartTimeInMillis": int(start * 1000),
                "lastEndTimeInMillis": int(end * 1000),
                "inputPipeline": True,
                "outputPipeline": True,
                "totalDrivers": 1,
                "operatorSummaries": self.operator_stats,
            }] if self.operator_stats else []),
        }

    def _runtime_stats(self) -> dict:
        """TaskStats.runtimeStats metrics (RuntimeMetric wire shape).
        Fragment-result-cache counters surface here so the coordinator
        can aggregate them into EXPLAIN ANALYZE."""
        out: dict = {}

        def metric(name: str, v: int):
            out[name] = {"name": name, "unit": "NONE", "sum": int(v),
                         "count": 1, "max": int(v), "min": int(v)}

        if self.cache_stats:
            metric("fragmentResultCacheHitCount",
                   self.cache_stats.get("hits", 0))
            metric("fragmentResultCacheMissCount",
                   self.cache_stats.get("misses", 0))
            metric("fragmentResultCacheEvictionCount",
                   self.cache_stats.get("evictions", 0))
            metric("fragmentResultCacheSizeBytes",
                   self.cache_stats.get("bytes", 0))
            metric("fragmentResultCacheHit", 1 if self.cache_hit else 0)
        if self.df_pruned:
            metric("dynamicFilterRowsPruned", self.df_pruned)
        return out

    def info(self, base_uri: str = "") -> S.TaskInfo:
        return S.TaskInfo(
            taskId=self.task_id, taskStatus=self.status(base_uri),
            lastHeartbeatInMillis=int(time.time() * 1000),
            noMoreSplits=sorted(self.splits) if self.no_more_splits else [],
            stats=self.stats_tree(),
            needsPlan=self.fragment is None, nodeId="tpu-worker-0")


class TpuTaskManager:
    """create/update/delete tasks; executes fragments on a worker thread
    so POST returns immediately (long-poll status sees RUNNING ->
    FINISHED, the coordinator's contract)."""

    def __init__(self, connector, base_uri: str = "",
                 cache_config=None, node_id: str = "tpu-worker-0",
                 spool_config=None, exchange_config=None,
                 memory_config=None, mesh_config=None):
        from presto_tpu.cache import FragmentResultCache
        from presto_tpu.config import (
            DEFAULT_CACHE, DEFAULT_EXCHANGE, DEFAULT_MEMORY, DEFAULT_SPOOL,
        )

        self.connector = connector
        # The worker, not the task, owns the jitted programs and the
        # capacities learned for them (exec/program_cache.py): a task's
        # executor lives for one fragment, and a repeated statement
        # would trace, lower and compile every program again.
        from presto_tpu.exec.program_cache import ProgramCache
        self.programs = ProgramCache()
        # cluster mesh execution tier (server/mesh_tier.py): owns this
        # worker's mesh slice, advertises it, and runs eligible task
        # fragments mesh-lowered with generic fallback
        from presto_tpu.server.mesh_tier import MeshTaskRunner
        self.mesh_tier = MeshTaskRunner(mesh_config)
        # worker memory pool (exec/memory.MemoryPool; reference:
        # MemoryPool.java): tasks reserve their static lowering
        # footprints at admission, keyed by task id so concurrent tasks
        # of one query account independently and roll up by prefix
        mcfg = memory_config if memory_config is not None \
            else DEFAULT_MEMORY
        self.memory_config = mcfg
        if mcfg.pool_bytes:
            from presto_tpu.exec.memory import MemoryPool
            self.memory_pool: Optional["MemoryPool"] = MemoryPool(
                mcfg.pool_bytes, mcfg.revoke_threshold)
        else:
            self.memory_pool = None
        self.base_uri = base_uri
        self.node_id = node_id
        # concurrent-exchange knobs for every upstream pull this worker
        # makes (protocol/exchange.ExchangeClient)
        self.exchange_config = (exchange_config
                                if exchange_config is not None
                                else DEFAULT_EXCHANGE)
        self.tasks: Dict[str, Task] = {}
        # spooled-exchange store (retry_policy=TASK): present only when
        # the process config enables it — per-query gating happens at
        # buffer-creation time from the session's retry_policy
        scfg = spool_config if spool_config is not None else DEFAULT_SPOOL
        if scfg.enabled:
            from presto_tpu.spool.store import SpoolStore
            self.spool: Optional["SpoolStore"] = SpoolStore(scfg)
        else:
            self.spool = None
        cfg = cache_config if cache_config is not None else DEFAULT_CACHE
        # worker-side fragment result store (consulted per task only
        # when the query enables fragment_result_cache_enabled)
        self.result_cache = (FragmentResultCache(
            cfg.budget_bytes, cfg.entry_cap())
            if cfg.enabled else None)
        self.total_bytes_out = 0      # monotonic (survives task delete)
        self.lifetime_tasks = 0       # monotonic created-task count
        import collections
        # DELETE-before-create tombstones: the deque keeps bounded FIFO
        # eviction order, the set makes the hot-path membership check
        # O(1) (create_or_update runs under self.lock for every POST)
        self.aborted_ids: "collections.deque" = collections.deque()
        self._aborted_set: set = set()
        self.lock = threading.Lock()
        # graceful-decommission lifecycle (reference: the native
        # worker's NodeState — ACTIVE until PUT /v1/info/state moves it
        # to SHUTTING_DOWN; new tasks are refused, running ones finish)
        self.lifecycle_state = "ACTIVE"
        self.drain_rejected = 0
        self.drain_seconds: Optional[float] = None

    # ------------------------------------------------------------------
    def create_or_update(self, task_id: str,
                         req: S.TaskUpdateRequest,
                         trace_ctx: Optional[TraceContext] = None
                         ) -> S.TaskInfo:
        if trace_ctx is None:
            return self._create_or_update(task_id, req, None)
        # on the HTTP handler's thread, under the propagated context:
        # registry, fragment decode, split binding, the run thread's start
        with TRACER.span(trace_ctx.trace_id, "task_create",
                         parent_id=trace_ctx.parent_span_id,
                         worker=self.node_id, task=task_id):
            return self._create_or_update(task_id, req, trace_ctx)

    def _create_or_update(self, task_id: str, req: S.TaskUpdateRequest,
                          trace_ctx: Optional[TraceContext]
                          ) -> S.TaskInfo:
        with self.lock:
            if self.lifecycle_state != "ACTIVE" \
                    and task_id not in self.tasks:
                # draining: refuse NEW work only — updates to tasks
                # already running here must still land so they can
                # finish and commit their spools
                self.drain_rejected += 1
                _M_DRAIN_REJECTS.inc()
                raise WorkerDrainingError(
                    f"worker {self.node_id} is SHUTTING_DOWN; "
                    f"task {task_id} must be scheduled elsewhere")
            if task_id in self._aborted_set:     # O(1) tombstone lookup
                # the task was aborted before it was created — never run
                # it (reference: TaskManager.cpp:564 out-of-order
                # delete/create handling)
                t = Task(task_id)
                t.set_state("ABORTED")
                return t.info(self.base_uri)
            task = self.tasks.get(task_id)
            if task is None:
                task = Task(task_id)
                self.tasks[task_id] = task
                self.lifetime_tasks += 1
                _M_TASKS_CREATED.inc()
        if trace_ctx is not None and task.trace_ctx is None:
            task.trace_ctx = trace_ctx
        # The update protocol is at-least-once and concurrent (coordinator
        # retries race the original POST): apply the whole update under
        # the task's lock, dedupe splits by sequenceId, and resolve split
        # targets against the STORED fragment so fragment-less later
        # updates still bind their splits.
        with task.update_lock:
            if req.outputIds is not None and task.buffers is None:
                # batch/materialized execution (presto-spark shuffle
                # role): output frames persist to disk and stay
                # replayable from token 0, enabling stage-level retry
                props = ((req.session.systemProperties or {})
                         if req.session is not None else {})
                mat = str(props.get(
                    "exchange_materialization_enabled", "")) \
                    .strip().lower() == "true"
                writer = None
                if self.spool is not None and str(props.get(
                        "retry_policy", "")).strip().upper() == "TASK":
                    # retry_policy=TASK: the output buffers ARE the
                    # spool part files; commit happens at FINISHED
                    try:
                        writer = self.spool.writer(task_id)
                    except ValueError:
                        writer = None    # unit-test style opaque ids
                task.buffers = OutputBufferManager(
                    sorted(req.outputIds.buffers), materialized=mat,
                    spool_writer=writer)
            if req.session is not None and req.session.systemProperties:
                task.session_properties.update(req.session.systemProperties)
            if req.fragment is not None and task.fragment is None:
                task.fragment = S.PlanFragment.from_bytes(req.fragment)
                task.scan_tables = _scan_tables(task.fragment)
            for src in req.sources:
                for ss in src.splits:
                    key = (src.planNodeId, ss.sequenceId)
                    if key in task.seen_splits:
                        continue
                    task.seen_splits.add(key)
                    task.pending_splits.append(ss)
                if src.noMoreSplits:
                    task.no_more_splits = True
            if task.fragment is not None:
                for ss in task.pending_splits:
                    cs = ss.split.connectorSplit or {}
                    if "location" in cs:
                        task.remote_splits.setdefault(
                            ss.planNodeId, []).append(
                            (cs["location"], str(cs.get("bufferId", "0"))))
                        continue
                    table = task.scan_tables.get(ss.planNodeId)
                    if table is not None:
                        # coordinator-pushed dynamic-filter constraint
                        # riding the scan split (one per scan node)
                        if isinstance(cs.get("constraint"), dict):
                            task.scan_constraints[table] = \
                                cs["constraint"]
                        # splits collapse BY TABLE: a fragment with two
                        # scan nodes over one table (fused cluster-mesh
                        # plans, self-joins) delivers the same split
                        # set once per node — an identical (part,
                        # numParts) pair is the same lifespan, and
                        # appending it again would double-read the scan
                        entry = (int(cs.get("part", 0)),
                                 int(cs.get("numParts", 1)))
                        bucket = task.splits.setdefault(table, [])
                        if entry not in bucket:
                            bucket.append(entry)
                task.pending_splits = []
            # A fragment with NO source nodes (pure VALUES / SELECT
            # without FROM) never receives a TaskSource, so no
            # noMoreSplits signal arrives — it is startable as soon as
            # the fragment and output buffers exist (the reference's
            # SqlTaskExecution treats a task with zero pending splits
            # per lifecycle the same way).
            sourceless = (task.fragment is not None
                          and not task.scan_tables
                          and not _fragment_has_remote_sources(
                              task.fragment))
            start = (task.fragment is not None
                     and (task.no_more_splits or sourceless)
                     and not task.pending_splits
                     and task.buffers is not None
                     and task.state == "PLANNED")
            if start:
                task.set_state("RUNNING")
        if start:
            spawn("worker", f"task-run-{task_id}", self._run,
                  args=(task,))
        return task.info(self.base_uri)

    # ------------------------------------------------------------------
    def _run(self, task: Task):
        ctx = task.trace_ctx
        if ctx is None:
            return self._run_inner(task)
        # worker-side span under the propagated context: this thread is
        # where the fragment actually executes, so scope + span both
        # live here; the coordinator scrapes them back at query end
        with trace_scope(ctx.trace_id, ctx.parent_span_id):
            with TRACER.span(ctx.trace_id, "task_run",
                             worker=self.node_id, task=task.task_id,
                             fragment=_fragment_of(task.task_id)) as sp:
                self._run_inner(task)
                sp.attributes["state"] = task.state

    def _run_inner(self, task: Task):
        try:
            from presto_tpu.config import PROPERTIES, Session
            from presto_tpu.protocol.validator import translate_validated

            with TRACER.span(None, "task_plan"):
                # Validate + translate (VeloxPlanValidator analog): foreign
                # connectors / unknown nodes / unsupported features fail with
                # a precise reason, not a mid-execution traceback.
                plan = translate_validated(task.fragment)
                ch = (task.session_properties or {}).get(
                    "x_dynamic_filter_channel")
                if ch is not None:
                    try:
                        task.df_channel = int(ch)
                    except (TypeError, ValueError):
                        task.df_channel = None
                if task.scan_constraints:
                    plan = self._apply_scan_constraints(task, plan)
                # Session properties arrive on the wire as strings
                # (SessionRepresentation.systemProperties); unknown ones are
                # coordinator-side and ignored here, like the C++ worker's
                # PrestoToVeloxQueryConfig mapping.
                known = {p.name for p in PROPERTIES}
                props = {k: v for k, v in
                         (task.session_properties or {}).items()
                         if k in known}
                # per-operator row counters feed the TaskInfo stats tree the
                # coordinator renders (OperatorStats role) — on by default
                props.setdefault("collect_stats", "true")
                ex = SplitExecutor(self.connector, session=Session(props),
                                   programs=self.programs)
                if self.memory_pool is not None:
                    # static footprints reserve against the worker pool as
                    # programs dispatch; the unique task-id key lets
                    # concurrent tasks of one query account independently
                    ex.memory_pool = self.memory_pool
                    ex.pool_query_id = task.task_id
                ex.set_splits(task.splits)
            task.total_splits = sum(len(v) for v in task.splits.values())
            task.start_time = time.time()
            # fragment result cache consult (Presto@Meta VLDB'23 §4.2):
            # an eligible leaf fragment whose key was produced before
            # replays its cached pages through the normal output-buffer
            # path — the exchange protocol cannot tell the difference
            cache_key = None
            caching_query = str(props.get(
                "fragment_result_cache_enabled", "")) \
                .strip().lower() == "true"
            if self.result_cache is not None and caching_query:
                cache_key = self._cache_key(task, plan)
            cached = (self.result_cache.get(cache_key)
                      if cache_key is not None else None)
            if cached is not None:
                task.cache_hit = True
                for page in cached:
                    task.output_positions += int(page.num_rows)
                    self._emit_output(task, page)
            else:
                if cache_key is not None:
                    task._cache_pages = []
                # cluster mesh tier first: an eligible fragment lowers
                # under the device mesh (server/mesh_tier.py); None
                # means fall through to the generic ladder unchanged
                mesh_out = self.mesh_tier.try_run(self, task, plan,
                                                  props)
                if mesh_out is not None:
                    page, mesh_ex = mesh_out
                    task.output_positions = int(page.num_rows)
                    self._collect_stats(task, mesh_ex)
                    self._emit_output(task, page)
                elif not self._run_streaming(task, plan, ex) \
                        and not self._run_streaming_remote(task, plan,
                                                           ex):
                    remote = self._pull_remote_inputs(task, plan)
                    ex.set_remote_pages(remote)
                    page = ex.execute(plan)
                    task.output_positions = int(page.num_rows)
                    self._collect_stats(task, ex)
                    self._emit_output(task, page)
                if cache_key is not None:
                    self.result_cache.put(
                        cache_key, getattr(task, "_cache_pages", []))
                    task._cache_pages = None
            if self.result_cache is not None and caching_query:
                task.cache_stats = self.result_cache.stats()
            task.end_time = time.time()
            task.cpu_nanos = int(
                (task.end_time - task.start_time) * 1e9)
            task.buffers.set_no_more_pages()
            # spool commit BEFORE the FINISHED transition: once any
            # observer can see FINISHED, the spool must already be
            # atomically published (rename-to-commit), or a consumer
            # racing the producer's death could find neither the HTTP
            # buffers nor a committed spool
            writer = getattr(task.buffers, "spool_writer", None)
            if writer is not None:
                writer.commit(str(task.instance_id))
            task.set_state("FINISHED")
        except Exception as e:
            from presto_tpu.exec.memory import ExceededMemoryLimitError
            from presto_tpu.protocol.validator import UnsupportedPlanError
            if isinstance(e, UnsupportedPlanError):
                # precise, coordinator-renderable reasons — no traceback
                task.failures.extend(e.reasons)
            elif isinstance(e, ExceededMemoryLimitError):
                # EXCEEDED_MEMORY_LIMIT class: the message alone is the
                # client contract (dbapi classifies on it) — a traceback
                # would bury it
                task.failures.append(str(e))
            else:
                task.failures.append(traceback.format_exc())
            if task.buffers is not None:
                if task.buffers.spool_writer is not None:
                    # retry_policy=TASK: consumers outlive this attempt
                    # (it is re-planned as attempt N+1), so its buffers
                    # must REFUSE, never end the stream: a consumer told
                    # `complete` here would finish without this task's
                    # rows. close() discards the unpublished spool and
                    # turns every GET into a 404, or into the
                    # replacement attempt's committed spool once there
                    # is one (http._closed_buffer_results)
                    task.buffers.close()
                else:
                    task.buffers.set_no_more_pages()
            task.set_state("FAILED")
        finally:
            if self.memory_pool is not None:
                self.memory_pool.free(task.task_id)

    def _cache_key(self, task: Task, plan) -> Optional[str]:
        """Cache key for this task's execution, or None when the
        fragment is ineligible: remote inputs (result depends on
        upstream task state, not table versions), table writers (side
        effects must run), or a connector without version tracking."""
        from presto_tpu.plan.fingerprint import fragment_cache_key
        from presto_tpu.plan.nodes import TableWriterNode, scan_tables_deep

        if _remote_source_nodes(plan):
            return None

        def has_writer(n) -> bool:
            return isinstance(n, TableWriterNode) or any(
                has_writer(c) for c in n.children())

        if has_writer(plan):
            return None
        version_of = getattr(self.connector, "table_version", None)
        if version_of is None:
            return None
        try:
            versions = [(t, int(version_of(t)))
                        for t in scan_tables_deep(plan)]
        except Exception:
            return None
        return fragment_cache_key(plan, versions, task.splits)

    def _apply_scan_constraints(self, task: Task, plan):
        """Push the coordinator's dynamic-filter constraints into this
        task's scans (reference: DynamicFilterService pushing summaries
        into not-yet-scheduled probe-side TableScan constraints).

        Two composing layers, both strictly row-removing on key values
        the build side cannot contain — correct for the INNER/SEMI probe
        paths the coordinator derives them from:
          1. split pruning: a split whose key min/max cannot intersect
             the domain is dropped whole (the parquet row-group-stats
             discipline of exec/lifespan; connectors without metadata
             stats fall back to one host-side column scan);
          2. residual FilterNode over the scan for the surviving splits.
        """
        from presto_tpu.expr.nodes import (
            Call, InputRef, Literal, SpecialForm, Form,
        )
        from presto_tpu.plan.nodes import FilterNode, TableScanNode
        from presto_tpu.types import BOOLEAN

        def coerce(t, v):
            return float(v) if t.dtype.kind == "f" else int(v)

        # ---- layer 1: whole-split pruning on key range ----------------
        for table, con in task.scan_constraints.items():
            splits = task.splits.get(table)
            if not splits or con.get("empty") \
                    or con.get("min") is None or con.get("max") is None:
                continue
            lo, hi = con["min"], con["max"]
            kept, dropped = [], []
            for (p, np_) in splits:
                try:
                    t = self.connector.table(table, part=p,
                                             num_parts=np_)
                    mm = (t.column_minmax(con["column"])
                          if hasattr(t, "column_minmax") else None)
                    if mm is None and t.num_rows:
                        sv = t.arrays[con["column"]][:t.num_rows]
                        mm = (sv.min(), sv.max())
                    pruned = (bool(mm[0] > hi or mm[1] < lo)
                              if mm is not None else False)
                except Exception:   # noqa: BLE001 — pruning is advisory
                    pruned = False
                (dropped if pruned else kept).append((p, np_))
            if not kept and dropped:
                # the executor needs at least one split bound; the
                # residual filter yields zero rows from it anyway
                kept.append(dropped.pop(0))
            for (p, np_) in dropped:
                task.df_pruned += int(self.connector.table(
                    table, part=p, num_parts=np_).num_rows)
            task.splits[table] = kept

        # ---- layer 2: residual FilterNode over each constrained scan --
        def predicate(con, ref, t):
            if con.get("empty"):
                # build produced zero rows: a contradiction the
                # compiler already supports (ge AND le with crossed
                # bounds) — every probe row is filtered
                return SpecialForm(Form.AND, (
                    Call("ge", (ref, Literal(coerce(t, 1), t)), BOOLEAN),
                    Call("le", (ref, Literal(coerce(t, 0), t)), BOOLEAN),
                ), BOOLEAN)
            if con.get("values"):
                return SpecialForm(
                    Form.IN,
                    (ref,) + tuple(Literal(coerce(t, v), t)
                                   for v in con["values"]), BOOLEAN)
            return SpecialForm(Form.AND, (
                Call("ge", (ref, Literal(coerce(t, con["min"]), t)),
                     BOOLEAN),
                Call("le", (ref, Literal(coerce(t, con["max"]), t)),
                     BOOLEAN),
            ), BOOLEAN)

        def rewrite(n):
            if isinstance(n, TableScanNode):
                con = task.scan_constraints.get(n.table)
                if con is not None and con.get("column") in n.columns:
                    ci = n.columns.index(con["column"])
                    t = n.output_types[ci]
                    if not t.is_string:
                        f = FilterNode(
                            n.output_names, n.output_types, source=n,
                            predicate=predicate(
                                con, InputRef(ci, t), t))
                        task._df_nodes.append((n, f))
                        return f
                return n
            names = [fld.name for fld in dataclasses.fields(n)]
            repl = {}
            if "probe" in names:
                repl = {"probe": rewrite(n.probe),
                        "build": rewrite(n.build)}
            elif "sources" in names:
                repl = {"sources": tuple(rewrite(s)
                                         for s in n.sources)}
            elif "source" in names and n.source is not None:
                repl = {"source": rewrite(n.source)}
            return dataclasses.replace(n, **repl) if repl else n

        return rewrite(plan)

    #: distinct build keys kept exactly per domain; past this only the
    #: [min, max] range survives (the reference's
    #: dynamic-filtering.max-distinct-values-per-driver role)
    DF_VALUES_CAP = 64

    def _accumulate_df_domain(self, task: Task, page: Page) -> None:
        """Fold one output page into the task's build-key domain summary
        (DynamicFilterSourceOperator role: min/max always, the exact
        distinct set while it stays small)."""
        ch = task.df_channel
        if ch is None or ch >= len(page.columns):
            return
        col = page.columns[ch]
        if col.type.is_string:
            return     # dictionary codes are per-task, not comparable
        d = task.df_domain
        if d is None:
            d = task.df_domain = {"min": None, "max": None,
                                  "values": set(), "count": 0}
        n = int(page.num_rows)
        if n == 0:
            return
        v, nl = col.to_numpy(n)
        v = np.asarray(v)[:n][~np.asarray(nl)[:n]]
        if not len(v):
            return
        as_py = (float if v.dtype.kind == "f" else int)
        lo, hi = as_py(v.min()), as_py(v.max())
        d["count"] += int(len(v))
        d["min"] = lo if d["min"] is None else min(d["min"], lo)
        d["max"] = hi if d["max"] is None else max(d["max"], hi)
        if isinstance(d["values"], set):
            d["values"].update(as_py(x) for x in np.unique(v))
            if len(d["values"]) > self.DF_VALUES_CAP:
                d["values"] = None     # range-only past the cap

    def _run_streaming(self, task: Task, plan, ex: SplitExecutor) -> bool:
        """Leaf-fragment streaming: execute one driving-scan lifespan at a
        time, emitting each batch's output into the token/ack buffers
        while the task is RUNNING — consumers observe token advances
        before this task finishes (reference: Driver.processFor
        incremental page flow through ClientBuffer, adapted to the
        batch-jit engine: the lifespan is the streaming quantum). Under a
        memory limit, lifespans subdivide until the static footprint
        fits, so a scan several times query_max_memory_per_node completes
        instead of failing. Returns False when the fragment shape needs
        single-shot execution (remote inputs / non-additive root)."""
        from presto_tpu.exec.executor import MemoryLimitExceeded
        from presto_tpu.exec.lifespan import _streamable
        from presto_tpu.plan.nodes import (
            AggregationNode, FilterNode, OutputNode, ProjectNode, Step,
        )

        if _remote_source_nodes(plan):
            return False
        driving, driving_rows = None, -1
        for table in task.splits:
            rows = self.connector.table(table).num_rows
            if rows > driving_rows:
                driving, driving_rows = table, rows
        if driving is None or not task.splits.get(driving):
            return False
        # Additive-root check: emitting per-lifespan outputs is correct
        # iff the union of batch outputs equals the single-shot output —
        # row-preserving pipelines, and PARTIAL aggregations (the
        # consumer's FINAL step merges partial states).
        node = plan
        while isinstance(node, (OutputNode, ProjectNode, FilterNode)):
            node = node.source
        if isinstance(node, AggregationNode):
            if node.step != Step.PARTIAL \
                    or not _streamable(node.source, driving):
                return False
        elif not _streamable(node, driving):
            return False

        base = list(task.splits[driving])
        sub = 1
        first: Optional[Page] = None
        while True:
            lifespans = [(p * sub + i, n * sub)
                         for (p, n) in base for i in range(sub)]
            try:
                ex.set_splits({**task.splits, driving: [lifespans[0]]})
                first = ex.execute(plan)
                break
            except MemoryLimitExceeded:
                # nothing emitted yet — safe to restart subdivided
                if sub >= 256:
                    raise
                sub *= 2
        # per-node row counters are per-execute; fold them across
        # lifespans so _collect_stats reports whole-task cardinalities
        acc: Dict[int, int] = {}

        def soak():
            for nid, r in (getattr(ex, "last_node_rows", None)
                           or {}).items():
                acc[nid] = acc.get(nid, 0) + int(r)

        soak()
        task.output_positions += int(first.num_rows)
        self._emit_output(task, first)
        for ls in lifespans[1:]:
            ex.set_splits({**task.splits, driving: [ls]})
            out = ex.execute(plan)
            soak()
            task.output_positions += int(out.num_rows)
            self._emit_output(task, out)
        ex.last_node_rows = acc
        self._collect_stats(task, ex)
        return True

    def _run_streaming_remote(self, task: Task, plan,
                              ex: SplitExecutor) -> bool:
        """Non-leaf streaming (reference: SqlTaskExecution.java:509 —
        every stage of a section runs concurrently, pages flowing
        through): a fragment whose DRIVING input is a RemoteSourceNode
        executes once per pulled chunk, emitting each chunk's output
        into the token/ack buffers while upstream tasks are still
        producing — so a 3-stage pipeline's stage-2 tokens advance
        before stage-1 finishes. Additivity rules are the lifespan
        rules (exec/lifespan._streamable_from): row-preserving chains
        and PARTIAL aggregations over the driving input; FINAL
        aggregations, sorts and join build sides fall back to
        single-shot. Returns False when the shape doesn't allow it."""
        from presto_tpu.exec.lifespan import _streamable_from
        from presto_tpu.plan.nodes import (
            AggregationNode, FilterNode, OutputNode, ProjectNode,
            RemoteSourceNode, Step,
        )
        from presto_tpu.protocol.exchange import ExchangeClient

        rs = _remote_source_nodes(plan)
        if not rs:
            return False
        # driving = the remote input with the most upstream tasks
        driving = max(rs, key=lambda n: len(
            task.remote_splits.get(n.node_id, [])))
        if not task.remote_splits.get(driving.node_id):
            return False

        def is_driving(n):
            return isinstance(n, RemoteSourceNode) \
                and n.node_id == driving.node_id

        node = plan
        while isinstance(node, (OutputNode, ProjectNode, FilterNode)):
            node = node.source
        if isinstance(node, AggregationNode):
            if node.step != Step.PARTIAL \
                    or not _streamable_from(node.source, is_driving):
                return False
        elif not _streamable_from(node, is_driving):
            return False

        # non-driving remote inputs materialize fully up front
        others = self._pull_remote_inputs(
            task, plan, skip={driving.node_id})
        ex.set_splits(task.splits)

        emitted = [0]
        acc: Dict[int, int] = {}

        def run_chunk(pages: List[Page]) -> None:
            if not pages:
                return
            for p in pages:
                p.names = driving.output_names
            chunk = _concat_upload(pages, driving.node_id)
            ex.set_remote_pages({**others, driving.node_id: chunk})
            out = ex.execute(plan)
            for nid, r in (getattr(ex, "last_node_rows", None)
                           or {}).items():
                acc[nid] = acc.get(nid, 0) + int(r)
            task.output_positions += int(out.num_rows)
            self._emit_output(task, out)
            emitted[0] += 1

        # concurrent pipelined pull (protocol/exchange.ExchangeClient):
        # every upstream task is fetched AND decoded by background
        # threads into the bounded buffer while run_chunk executes, so
        # the shuffle costs ~max-of-streams instead of ~sum and the
        # device never idles through a GET; chunks interleave across
        # upstreams in arrival order (legal here — additivity already
        # allows any chunking of the driving input)
        with ExchangeClient(task.remote_splits[driving.node_id],
                            types=list(driving.output_types),
                            config=self.exchange_config,
                            spool=self.spool) as xc:
            landed = 0
            while True:
                # a container: the fetchers' GETs that land data are
                # the `exchange_pull` spans; bytes are what landed since
                # the last chunk (the sum is exact)
                with TRACER.span(None, "exchange_wait",
                                 source=driving.node_id,
                                 upstreams=len(xc._streams)) as sp:
                    pages = xc.next_chunk()
                    sp.attributes.update(
                        bytes=xc.bytes_pulled - landed,
                        pages=len(pages or ()))
                    landed += sp.attributes["bytes"]
                if pages is None:
                    break
                run_chunk(pages)
        if emitted[0] == 0:
            # no upstream rows at all: run once on an empty chunk so
            # output shape/stats exist (PARTIAL aggs emit zero states)
            from presto_tpu.data.column import Column
            cols = [Column.from_numpy(np.zeros(0, t.dtype), t,
                                      capacity=256)
                    for t in driving.output_types]
            run_chunk([Page.from_columns(cols, 0,
                                         driving.output_names)])
        ex.last_node_rows = acc
        self._collect_stats(task, ex)
        return True

    def _collect_stats(self, task: Task, ex: SplitExecutor) -> None:
        """Executor per-node row counters -> OperatorStats summaries
        (reference: PrestoTask.cpp converting velox stats to protocol
        OperatorStats; planNodeId/operatorType/outputPositions are the
        fields the coordinator's UI and EXPLAIN ANALYZE consume)."""
        from presto_tpu.plan.nodes import TableScanNode
        from presto_tpu.plan.stats import canonical_key
        task.memory_bytes = int(
            getattr(ex, "last_memory_estimate", 0) or 0)
        rows = getattr(ex, "last_node_rows", None) or {}
        node_map = getattr(ex, "_node_map", {}) or {}
        summaries = []
        raw_in = 0
        for op_id, (nid, out_rows) in enumerate(sorted(rows.items())):
            entry = node_map.get(nid)
            node = entry[0] if entry else None
            op_type = type(node).__name__ if node is not None else "?"
            if isinstance(node, TableScanNode):
                raw_in += int(out_rows)
            summary = {
                "pipelineId": 0,
                "operatorId": op_id,
                "planNodeId": str(nid),
                "operatorType": op_type.replace("Node", "Operator"),
                "totalDrivers": 1,
                "outputPositions": int(out_rows),
                "outputDataSizeInBytes": 0,
            }
            if node is not None:
                # structural digest the coordinator folds into its
                # HistoryStore — worker-local subtrees (scan/filter
                # chains) hash identically to the planner's subtrees,
                # which is exactly where history informs estimates
                try:
                    summary["canonicalKey"] = canonical_key(node)
                except Exception:  # noqa: BLE001 — stats stay best-effort
                    pass
            summaries.append(summary)
        task.raw_input_positions = raw_in
        task.operator_stats = summaries
        # dynamic-filter effectiveness: rows the injected residual
        # filter removed on top of whole-split pruning (delta is
        # unavailable when the filter fused into its parent — fine,
        # split-level pruning still counted)
        if task._df_nodes:
            # Locate the injected filter/scan pair STRUCTURALLY: the
            # executor rebuilds subtrees (island copies), so identity
            # does not survive — but the predicate is a frozen
            # dataclass tree and compares by value. The scan nid comes
            # from the filter copy's own source, which shares the
            # rebuilt tree.
            from presto_tpu.plan.nodes import FilterNode
            nid_of = {id(n): nid for nid, (n, _c) in node_map.items()}
            wanted = {(s.table, f.predicate)
                      for s, f in task._df_nodes}
            for f_nid, (n, _c) in node_map.items():
                if not (isinstance(n, FilterNode)
                        and isinstance(n.source, TableScanNode)
                        and (n.source.table, n.predicate) in wanted):
                    continue
                s_nid = nid_of.get(id(n.source))
                if s_nid in rows and f_nid in rows:
                    task.df_pruned += max(
                        0, int(rows[s_nid]) - int(rows[f_nid]))
        if task.df_pruned:
            _M_DF_PRUNED.inc(task.df_pruned)
        # per-operator worker spans from the island profile: each island
        # was timed where it ran (start and seconds on the span clock)
        ctx = task.trace_ctx
        profile = getattr(ex, "last_island_profile", None) or []
        if ctx is not None:
            for entry in profile:
                TRACER.record(
                    ctx.trace_id, f"op:{entry.get('root', '?')}",
                    entry["t0"], end=entry["t0"] + entry["seconds"],
                    parent_id=ctx.parent_span_id,
                    worker=self.node_id, task=task.task_id,
                    rows=int(entry.get("rows", 0) or 0))

    #: Each GET to an upstream buffer returns at most this many bytes
    #: (client-side backpressure; reference: ExchangeClient's
    #: maxResponseSize). Chunks decode to engine pages immediately, so
    #: raw wire bytes never accumulate past one chunk per upstream.
    REMOTE_CHUNK_BYTES = 4 << 20

    def _pull_remote_inputs(self, task: Task, plan,
                            skip=None) -> Dict[str, Page]:
        """Pull every upstream page stream this task's remote splits name
        in bounded chunks and fuse them into one engine Page per
        RemoteSourceNode (consumer side of the pull protocol —
        ExchangeClient.java:255 semantics; the final materialization is
        what the whole-fragment jit engine consumes). `skip` excludes
        node ids the caller streams itself (_run_streaming_remote).
        Pulls ride the concurrent ExchangeClient: producer latencies
        overlap AND decoded residency is bounded by
        `ExchangeConfig.max_buffered_bytes` ahead of the consumer
        (the old thread-per-location drain accumulated every upstream's
        pages unboundedly before the join)."""
        from presto_tpu.protocol.exchange import ExchangeClient

        out: Dict[str, Page] = {}
        for node in _remote_source_nodes(plan):
            if skip and node.node_id in skip:
                continue
            splits = task.remote_splits.get(node.node_id, [])
            with TRACER.span(None, "exchange_wait", source=node.node_id,
                             upstreams=len(splits)) as sp:
                out[node.node_id] = self._pull_one(node, splits, sp)
        return out

    def _pull_one(self, node, splits, span) -> Page:
        """One RemoteSourceNode's upstream buffers, drained and fused
        into one page on the device."""
        from presto_tpu.protocol.exchange import ExchangeClient

        pages: List[Page] = []
        if splits:
            with ExchangeClient(splits, types=list(node.output_types),
                                config=self.exchange_config,
                                spool=self.spool) as xc:
                pages = xc.drain_pages()
                span.attributes.update(bytes=xc.bytes_pulled,
                                       pages=len(pages))
        if not pages:
            # no producer emitted rows: empty page of the right shape
            from presto_tpu.data.column import Column
            cols = [Column.from_numpy(
                np.zeros(0, t.dtype), t, capacity=256)
                for t in node.output_types]
            return Page.from_columns(cols, 0, node.output_names)
        for p in pages:
            p.names = node.output_names
        return _concat_upload(pages, node.node_id)

    def _emit_output(self, task: Task, page: Page):
        """Route the fragment result into output buffers per the
        fragment's PartitioningScheme (producer side of the exchange:
        PartitionedOutputOperator.java:57 hash split,
        BroadcastOutputBuffer replication, TaskOutputOperator single)."""
        if task._cache_pages is not None:
            # record the pre-partitioning page for the cache populate
            # step (replay re-partitions, so a later consumer-count
            # change still routes correctly)
            task._cache_pages.append(page)
        if task.df_channel is not None:
            # build-side fragment: summarize the join-key domain from
            # the pre-partitioning page (DynamicFilterSourceOperator)
            self._accumulate_df_domain(task, page)
        with TRACER.span(None, "download",
                         bytes=page_nbytes(page)):
            page_to_host(page)
        with TRACER.span(None, "serialize", device_puts=0,
                         buffers=len(task.buffers.buffers)) as sp:
            before = task.bytes_out
            self._route_output(task, page)
            sp.attributes["bytes"] = task.bytes_out - before

    def _route_output(self, task: Task, page: Page) -> None:
        """Partition, serialize, compress and buffer one output page
        (its arrays already on the host). A partition is a host page:
        the `serialize` span's `device_puts` counts the arrays of the
        partitions that are on the device all the same (none)."""
        codec = (task.session_properties or {}).get(
            "exchange_compression_codec")
        if codec in (None, "", "none"):
            codec = None
        scheme = task.fragment.partitioningScheme
        handle = ((scheme.partitioning.handle.connectorHandle or {})
                  if scheme and scheme.partitioning else {})
        kind = handle.get("partitioning", "SINGLE")
        buffer_ids = sorted(
            task.buffers.buffers,
            key=lambda b: (0, int(b)) if b.isdigit() else (1, b))
        nbuf = len(buffer_ids)

        def emit(buffer_id: str, frame: bytes):
            task.bytes_out += len(frame)
            with self.lock:
                self.total_bytes_out += len(frame)
            task.buffers.add_page(buffer_id, frame)

        def partition(idx: np.ndarray) -> bytes:
            part = select_page_host(page, idx)
            TRACER.add("serialize",
                       device_puts=note_exchange_pages("partition", [part]))
            return self._serialize(part, codec)

        if kind in ("FIXED_BROADCAST_DISTRIBUTION", "SINGLE") \
                and nbuf > 1:
            # BROADCAST — and SINGLE gathers shared by several consumers:
            # every buffer receives the full output (each consumer task
            # owns one buffer; token/ack state is per-buffer).
            frame = self._serialize(page, codec)
            for b in buffer_ids:
                emit(b, frame)
            return
        if kind in ("FIXED_ARBITRARY_DISTRIBUTION",
                    "ARBITRARY_DISTRIBUTION") and nbuf > 1:
            # round-robin repartition (reference: ArbitraryOutputBuffer)
            n = int(page.num_rows)
            for b_idx, b in enumerate(buffer_ids):
                idx = np.arange(b_idx, n, nbuf)
                emit(b, partition(idx))
            return
        if kind != "FIXED_HASH_DISTRIBUTION" and nbuf > 1:
            raise NotImplementedError(
                f"output partitioning {kind} with {nbuf} buffers")
        if kind == "FIXED_HASH_DISTRIBUTION" and nbuf > 1:
            layout = {v.name: i for i, v in enumerate(scheme.outputLayout)}
            channels = tuple(layout[v.name]
                             for v in scheme.partitioning.arguments)
            pid = _hash_partition_ids(page, channels, nbuf)
            for b_idx, b in enumerate(buffer_ids):
                idx = np.nonzero(pid == b_idx)[0]
                emit(b, partition(idx))
            return
        # SINGLE (and the 1-buffer degenerate of every other scheme)
        emit(buffer_ids[0], self._serialize(page, codec))

    def _serialize(self, page: Page, codec=None) -> bytes:
        blocks = page_to_wire_blocks(page)
        return encode_serialized_page(blocks, checksummed=True,
                                      compression=codec)

    # ------------------------------------------------------------------
    def get(self, task_id: str) -> Optional[Task]:
        return self.tasks.get(task_id)

    def get_status(self, task_id: str, current_state: Optional[str],
                   max_wait_s: float) -> Optional[S.TaskStatus]:
        """Long-poll: return when the state differs from current_state or
        the wait expires (X-Presto-Current-State / X-Presto-Max-Wait)."""
        task = self.tasks.get(task_id)
        if task is None:
            return None
        deadline = time.time() + max_wait_s
        with task.state_change:
            while (current_state is not None
                   and task.state == current_state
                   and time.time() < deadline):
                task.state_change.wait(
                    max(0.0, deadline - time.time()))
        return task.status(self.base_uri)

    def task_rows(self) -> List[dict]:
        """Per-task summary rows for GET /v1/tasks — the worker-side
        feed of `system.runtime.tasks` (connectors/system_runtime.py).
        One locked snapshot of the task map; per-task fields read
        without per-task locks (monotone counters, point-in-time)."""
        with self.lock:
            tasks = list(self.tasks.values())
        now = time.time()
        rows = []
        for t in tasks:
            start = t.start_time
            wall = ((t.end_time or now) - start) if start else 0.0
            rows.append({
                "nodeId": self.node_id,
                "taskId": t.task_id,
                "state": t.state,
                "splits": t.total_splits,
                "bytesOut": t.bytes_out,
                "outputRows": t.output_positions,
                "cacheHit": bool(t.cache_hit),
                "dfPruned": int(t.df_pruned),
                "wallS": round(wall, 6),
                "traceId": (t.trace_ctx.trace_id
                            if t.trace_ctx is not None else None),
            })
        return rows

    #: tombstone bound (the reference caps its zombie task list too) —
    #: enough to cover any realistic coordinator retry window
    MAX_TOMBSTONES = 4096

    def delete(self, task_id: str) -> Optional[S.TaskInfo]:
        with self.lock:
            # pop + tombstone under ONE lock acquisition: a concurrent
            # create must observe either the live task or the tombstone,
            # never neither (TaskManager.cpp:564 ordering)
            task = self.tasks.pop(task_id, None)
            if task is None and task_id not in self._aborted_set:
                self.aborted_ids.append(task_id)
                self._aborted_set.add(task_id)
                if len(self.aborted_ids) > self.MAX_TOMBSTONES:
                    self._aborted_set.discard(self.aborted_ids.popleft())
        if task is None:
            t = Task(task_id)
            t.set_state("ABORTED")
            return t.info(self.base_uri)
        if task.state in ("PLANNED", "RUNNING"):
            task.set_state("ABORTED")
        if task.buffers is not None:
            task.buffers.close()     # materialized shuffle files
        return task.info(self.base_uri)

    def drain(self, timeout_s: float = 30.0,
              poll_s: float = 0.05) -> dict:
        """Graceful decommission (reference: the native worker's
        shutdown handler draining tasks before exit): flip the
        lifecycle to SHUTTING_DOWN so new task creations are refused,
        then wait — up to `timeout_s` — for every PLANNED/RUNNING task
        to reach a terminal state. Spool commits happen inside the task
        run path before FINISHED, so a clean drain leaves every output
        either served or atomically committed to the spool. Idempotent;
        only the first call observes the drain histogram."""
        with self.lock:
            first = self.lifecycle_state == "ACTIVE"
            self.lifecycle_state = "SHUTTING_DOWN"
        # a draining worker must stop advertising its mesh slice
        # IMMEDIATELY — new stages must never co-locate onto a mesh
        # that is leaving (coordinator probes /v1/mesh fresh per query)
        self.mesh_tier.retract()
        t0 = time.time()
        deadline = t0 + max(timeout_s, 0.0)
        while True:
            with self.lock:
                live = [t.task_id for t in self.tasks.values()
                        if t.state in ("PLANNED", "RUNNING")]
            if not live or time.time() >= deadline:
                break
            time.sleep(poll_s)
        took = time.time() - t0
        if first:
            self.drain_seconds = took
            _M_DRAIN_SECONDS.observe(took)
        return {"state": self.lifecycle_state,
                "drain_seconds": round(took, 4),
                "tasks_remaining": len(live),
                "remaining_task_ids": live[:16],
                "rejected": self.drain_rejected}

    def shutdown(self):
        """Release every live task's disk-backed output on worker stop.
        DELETE normally closes buffers task by task, but a worker
        stopped mid-query (tests, rolling restarts) still holds tasks
        the coordinator could never reach — without this their
        materialized-shuffle FrameFiles outlive the process's work."""
        with self.lock:
            tasks = list(self.tasks.values())
            self.tasks.clear()
        for task in tasks:
            if task.state in ("PLANNED", "RUNNING"):
                task.set_state("ABORTED")
            if task.buffers is not None:
                try:
                    task.buffers.close()
                except OSError:
                    pass
        if self.spool is not None:
            self.spool.close()

    @staticmethod
    def _loc_task_id(location: str) -> str:
        """The task-id path segment of an upstream location URI."""
        return location.rstrip("/").rsplit("/", 1)[-1]

    def remove_remote_source(self, task_id: str,
                             remote_source_task_id: str) -> bool:
        """DELETE /v1/task/{id}/remote-source/{sourceId} (reference:
        TaskResource.cpp removeRemoteSource): drop the given upstream
        task's splits so future pulls skip it. Matches the exact
        task-id path segment (never a substring — '1.0.0' must not
        drop '11.0.0')."""
        task = self.tasks.get(task_id)
        if task is None:
            return False
        with self.lock:
            for nid, splits in list(task.remote_splits.items()):
                task.remote_splits[nid] = [
                    (loc, buf) for loc, buf in splits
                    if self._loc_task_id(loc) != remote_source_task_id]
        return True

    def memory_bytes(self) -> int:
        return sum(t.bytes_out for t in self.tasks.values())

    def pool_stats(self) -> dict:
        """Worker memory-pool snapshot for /v1/memory and the
        coordinator's heartbeat scrape: budget, reserved, and per-QUERY
        reservations (task-id keys rolled up by their query prefix)."""
        pool = self.memory_pool
        if pool is None:
            return {"budgetBytes": 0, "reservedBytes": 0,
                    "revocations": 0, "revokedBytes": 0,
                    "queryReservations": {}}
        with pool._lock:
            by_key = dict(pool._by_query)
        by_query: Dict[str, int] = {}
        for key, b in by_key.items():
            qid = key.split(".", 1)[0]
            by_query[qid] = by_query.get(qid, 0) + b
        return {"budgetBytes": pool.budget,
                "reservedBytes": sum(by_key.values()),
                "revocations": pool.revocations,
                "revokedBytes": pool.revoked_bytes,
                "queryReservations": by_query}

    def record_gauges(self) -> None:
        """Refresh scrape-time gauges (tasks by state, queue depths).
        Called from the /v1/metrics handler: gauges describe NOW, so
        computing them at scrape time beats updating on every
        transition (tasks don't know their manager)."""
        with self.lock:
            tasks = list(self.tasks.values())
        counts = {s: 0 for s in _TASK_STATES}
        pending = 0
        out_bytes = 0
        for t in tasks:
            counts[t.state] = counts.get(t.state, 0) + 1
            pending += len(t.pending_splits)
            out_bytes += t.bytes_out
        for state, n in counts.items():
            _M_TASKS_BY_STATE.set(n, state=state)
        _M_PENDING_SPLITS.set(pending)
        _M_OUTPUT_BYTES.set(out_bytes)
        _M_TASKS_LIVE.set(len(tasks))
        _M_LIFETIME_BYTES.set(self.total_bytes_out)
