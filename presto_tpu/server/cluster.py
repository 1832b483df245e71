"""Multi-worker cluster: a coordinator-side scheduler driving N TPU
workers over the real task protocol.

Reference roles folded into TpuCluster:
  - SqlQueryScheduler / SectionExecutionFactory
    (execution/scheduler/SqlQueryScheduler.java:115,356): walk the
    fragment tree leaf-first, decide task counts and placement.
  - HttpRemoteTask (server/remotetask/HttpRemoteTaskWithEventLoop.java:981):
    build TaskUpdateRequests (fragment bytes, splits, output buffer ids)
    and POST them to /v1/task/{taskId}.
  - StageLinkage: wire producer task locations into consumer tasks as
    remote splits (RemoteSplit.location -> the producer's results URI).
  - the coordinator's root-stage ExchangeClient: pull the root fragment's
    buffers and decode rows for the client.

Every byte between coordinator and workers rides HTTP exactly as the
Java/C++ pairing does; inside each worker the fragment still executes as
one jit program (and on a real multi-chip worker, over the ICI mesh via
the DistExecutor — HTTP across hosts, collectives within a host,
SURVEY.md §5.8)."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import random
import threading
import time
import uuid
from typing import Dict, List, Optional, Tuple

from presto_tpu.admission.groups import current_admission
from presto_tpu.config import DEFAULT_OBS, TransportConfig
from presto_tpu.obs.metrics import counter as _obs_counter, \
    gauge as _obs_gauge
from presto_tpu.plan.fragment import add_exchanges, create_fragments
from presto_tpu.plan.iterative import reorder_joins
from presto_tpu.plan.stats import (
    HistoryStore, canonical_key, default_history_path, estimate_rows,
)
from presto_tpu.utils.threads import spawn
from presto_tpu.utils.tracing import TRACER, now, root_scope, trace_scope
from presto_tpu.plan.nodes import ExchangeNode, Partitioning, PlanNode
from presto_tpu.protocol import structs as S
from presto_tpu.protocol.exchange import (
    ExchangeClient, exchange_counters, stream_pages,
)
from presto_tpu.protocol.to_protocol import FragmentSpec, \
    constrain_split_payload, fragment_to_protocol, remote_split_payload
from presto_tpu.protocol.transport import (FatalResponseError,
                                           HttpClient, TransportError)
from presto_tpu.server.http import TpuWorkerServer

log = logging.getLogger("presto_tpu.cluster")

_M_MERGE_HIGH = _obs_gauge(
    "presto_tpu_merge_inflight_high_water",
    "Max in-flight row batches during bounded k-way root merges")

# elastic-membership counters (Presto@Meta VLDB'23 §3 fluid worker
# membership): admissions into, and departures from, the schedulable set
_M_MEMBER_JOINS = _obs_counter(
    "presto_tpu_membership_joins_total",
    "Workers admitted to the schedulable set (first announcement or "
    "re-admission after death/drain)")
_M_MEMBER_DEPARTURES = _obs_counter(
    "presto_tpu_membership_departures_total",
    "Workers removed from the schedulable set by the failure detector")
_M_MEMBER_DRAINS = _obs_counter(
    "presto_tpu_membership_drains_total",
    "Workers that left the schedulable set via graceful decommission "
    "(SHUTTING_DOWN)")


def _unshare(plan: PlanNode) -> PlanNode:
    """Duplicate shared subtrees (mark joins reference the probe pipeline
    twice) so the fragmenter emits independent producer fragments per
    consumer. The in-worker ICI path evaluates shared subtrees once; the
    HTTP path re-executes them — the reference does the same unless CTE
    materialization is enabled (optimizations/PhysicalCteOptimizer.java)."""
    import copy

    seen = set()

    def visit(n: PlanNode) -> PlanNode:
        if id(n) in seen:
            n = copy.deepcopy(n)
        seen.add(id(n))
        kids = n.children()
        if not kids:
            return n
        repl = {}
        names = [f.name for f in dataclasses.fields(n)]
        if "probe" in names:
            repl["probe"] = visit(n.probe)
            repl["build"] = visit(n.build)
        elif "source" in names and n.source is not None:
            repl["source"] = visit(n.source)
        return dataclasses.replace(n, **repl)

    return visit(plan)


def _derange(plan: PlanNode):
    """Distributed ORDER BY in the HTTP cluster: the ROOT sort's RANGE
    exchange is dropped entirely — each task sorts its own shard and the
    COORDINATOR k-way merges the sorted page streams (the ordered merge
    exchange, operator/MergeOperator.java + MergeHashSort.java). Peak
    per-worker memory stays O(shard); the coordinator holds one page per
    stream. Returns (plan', merge_keys or None). Any OTHER RANGE
    exchange (nested sorts) still degrades to a SINGLE gather: range
    splitters need a sampling pass the streaming protocol doesn't carry;
    the in-worker ICI path (DistExecutor) keeps true range exchanges."""
    from presto_tpu.plan.nodes import OutputNode, SortNode

    merge_keys = None
    if isinstance(plan, OutputNode) \
            and isinstance(plan.source, SortNode) \
            and isinstance(plan.source.source, ExchangeNode) \
            and plan.source.source.partitioning == Partitioning.RANGE:
        sort = plan.source
        local_sort = dataclasses.replace(sort, source=sort.source.source)
        plan = dataclasses.replace(plan, source=local_sort)
        merge_keys = tuple(sort.keys)

    def visit(n: PlanNode) -> PlanNode:
        kids = n.children()
        if not kids:
            return n
        repl = {}
        names = [f.name for f in dataclasses.fields(n)]
        if "probe" in names:
            repl["probe"] = visit(n.probe)
            repl["build"] = visit(n.build)
        elif "source" in names and n.source is not None:
            repl["source"] = visit(n.source)
        n = dataclasses.replace(n, **repl)
        if isinstance(n, ExchangeNode) \
                and n.partitioning == Partitioning.RANGE:
            n = dataclasses.replace(n, partitioning=Partitioning.SINGLE,
                                    keys=(), sort_keys=())
        return n
    return visit(plan), merge_keys


def bounded_merge(batch_sources, key, queue_pages=4):
    """K-way merge of pre-sorted row-batch streams under a COORDINATOR
    memory bound (reference: MergeOperator + ExchangeClient's
    maxBufferedBytes back-pressure). One producer thread per stream
    decodes batches into a `queue.Queue(maxsize=queue_pages)`; a full
    queue blocks its producer (and, through the page protocol, stops
    acknowledging frames), so at most ``k * (queue_pages + 2)`` row
    batches exist coordinator-side at once instead of every run fully
    materialized before the merge. The consumer side feeds
    ``heapq.merge`` — streams stay sorted, output is the total order.

    ``batch_sources`` is a list of zero-arg callables each returning an
    iterator of row batches (lists of tuples). Returns
    ``(rows, in_flight_high_water)``. The first real producer failure is
    re-raised after all producers stop; sibling streams abort instead of
    draining to completion. The merging loop is the `root_merge` span:
    `blocked_ms` is its time blocked in a stream's `get` (not named
    `waited_ms`, which marks a back-dated marker to the trace's reader),
    `timeouts` the gets that ran out their 0.05 s."""
    import heapq
    import queue as _queue

    n = len(batch_sources)
    if n == 0:
        return [], 0
    queues = [_queue.Queue(maxsize=queue_pages) for _ in range(n)]
    done = [False] * n
    failed = threading.Event()
    cause: List[BaseException] = []
    lock = threading.Lock()
    in_flight = [0]
    high_water = [0]

    def produce(i):
        try:
            for batch in batch_sources[i]():
                if not batch:
                    continue
                with lock:
                    in_flight[0] += 1
                    if in_flight[0] > high_water[0]:
                        high_water[0] = in_flight[0]
                while True:
                    if failed.is_set():
                        return
                    try:
                        queues[i].put(batch, timeout=0.05)
                        break
                    except _queue.Full:
                        continue
        except BaseException as e:   # noqa: BLE001 — propagated below
            if not failed.is_set():
                cause.append(e)      # the REAL failure, not a sibling's
            failed.set()             # abort placeholder
        finally:
            done[i] = True

    # the merging thread's seconds blocked in `get`, the gets that ran
    # out their timeout, the batches merged: `root_merge`'s attributes
    blocked_s = [0.0]
    timeouts = [0]
    batches = [0]

    def stream(i):
        while True:
            t0 = time.perf_counter()
            try:
                batch = queues[i].get(timeout=0.05)
            except _queue.Empty:
                blocked_s[0] += time.perf_counter() - t0
                timeouts[0] += 1
                if failed.is_set():
                    raise ClusterQueryError(
                        "merge input stream failed; aborting merge")
                if done[i] and queues[i].empty():
                    return
                continue
            blocked_s[0] += time.perf_counter() - t0
            batches[0] += 1
            with lock:
                in_flight[0] -= 1
            for row in batch:
                yield row

    threads = [spawn("coordinator", f"merge-produce-{i}", produce,
                     args=(i,), start=False)
               for i in range(n)]
    for t in threads:
        t.start()
    try:
        with TRACER.span(None, "root_merge", streams=n) as sp:
            try:
                rows = list(heapq.merge(*(stream(i) for i in range(n)),
                                        key=key))
            finally:
                sp.attributes.update(blocked_ms=1e3 * blocked_s[0],
                                     timeouts=timeouts[0],
                                     batches=batches[0])
    except BaseException:
        failed.set()                 # release blocked producers
        for t in threads:
            t.join(timeout=5)
        if cause:
            raise cause[0]
        raise
    for t in threads:
        t.join(timeout=5)
    if cause:
        raise cause[0]
    return rows, high_water[0]


@dataclasses.dataclass
class _Stage:
    spec: FragmentSpec
    n_tasks: int
    n_buffers: int
    # consumer fragment id -> first buffer index it owns (shared
    # SINGLE/BROADCAST producers give each consumer a disjoint range)
    buffer_offset: Dict[int, int] = dataclasses.field(default_factory=dict)
    task_ids: List[str] = dataclasses.field(default_factory=list)
    task_uris: List[str] = dataclasses.field(default_factory=list)
    # scan-node id -> (connector id, per-task split payloads); kept so
    # task-level recovery re-posts the SAME lifespans elsewhere
    scan_splits: Dict = dataclasses.field(default_factory=dict)
    recovered_tasks: int = 0
    # retry_policy=TASK bookkeeping: task indices whose COMMITTED spool
    # absorbed a dead worker (never re-executed, never re-polled), and
    # the committed attempt's task id consumers should read
    spool_done: set = dataclasses.field(default_factory=set)
    spool_task_ids: Dict[int, str] = dataclasses.field(
        default_factory=dict)
    # cross-exchange dynamic filtering (reference: DynamicFilterService):
    # a build stage publishes its join-key domain on this output channel;
    # a probe stage carries the spec of the filter it should wait for,
    # and — once merged — the constraint injected into its scan splits.
    # The constraint lives HERE so recovery re-posts reproduce it.
    df_publish_channel: Optional[int] = None
    df_spec: Optional[dict] = None
    df_constraint: Optional[dict] = None
    # cluster mesh tier (server/mesh_tier.py): the mesh worker this
    # fused stage should land on, and the ICI exchange descriptor its
    # task properties carry. Kept on the stage so recovery re-posts
    # re-stamp the SAME descriptor (a survivor re-runs mesh-lowered or
    # falls back generic — either way oracle-exact).
    mesh_worker: Optional[str] = None
    mesh_descriptor: Optional[dict] = None


class ClusterQueryError(RuntimeError):
    pass


class ClusterMemoryKillError(ClusterQueryError):
    """EXCEEDED_MEMORY_LIMIT class: the cluster low-memory killer chose
    this query (ClusterMemoryManager.maybe_kill). Terminal — recovery
    paths must NEVER retry or re-execute a killed query, even under
    retry_policy=TASK."""


class _ClusterSubqueryExec:
    """Adapter exposing Executor._resolve_subqueries over the cluster:
    `execute` routes nested plans through the cluster and returns rows."""

    def __init__(self, cluster: "TpuCluster"):
        self.cluster = cluster

    def execute(self, plan):
        return self.cluster._execute_plan(plan)

    def _page_rows(self, rows):
        return rows

    def _resolve_subqueries(self, plan):
        from presto_tpu.exec.executor import Executor
        return Executor._resolve_subqueries(self, plan)


class TpuCluster:
    """N in-process workers + the scheduler. `workers` may also be
    attached to externally-started servers via `worker_uris`."""

    def __init__(self, connector, n_workers: int = 2,
                 session_properties: Optional[Dict[str, str]] = None,
                 resource_groups=None, history=None, discovery=None,
                 shared_secret: Optional[str] = None,
                 transport_config: Optional[TransportConfig] = None,
                 cache_config=None, spool_config=None,
                 exchange_config=None, mv_config=None,
                 mv_journal_path: Optional[str] = None,
                 memory_config=None, obs_config=None,
                 mesh_config=None):
        import dataclasses as _dc

        from presto_tpu.cache import AffinityRouter
        from presto_tpu.config import DEFAULT_EXCHANGE, DEFAULT_SPOOL
        from presto_tpu.admission.groups import ResourceGroupManager
        from presto_tpu.sql.analyzer import Planner

        # internal-communication JWT (InternalCommunicationConfig
        # sharedSecret + internalJwtEnabled): the coordinator signs its
        # requests; workers enforce
        self.shared_secret = shared_secret
        if shared_secret:
            from presto_tpu.server.auth import configure
            configure(shared_secret, "tpu-coordinator")

        # introspection facade: `system.*` tables answer from this
        # cluster's live state, everything else delegates to the real
        # connector. Wrapped FIRST so the planner and every in-process
        # worker (which share the object) see one catalog; the cluster
        # reference is attached at the end of construction.
        from presto_tpu.connectors.system_runtime import \
            SystemTablesConnector
        if not isinstance(connector, SystemTablesConnector):
            connector = SystemTablesConnector(connector)

        self.connector = connector
        self.planner = Planner(connector)
        # HBO store (plan/stats.HistoryStore) consulted by AddExchanges'
        # broadcast-vs-repartition costing AND fed back from the workers'
        # observed cardinalities at query end (cluster-fed HBO; reference:
        # HistoryBasedPlanStatisticsCalculator.java:58 paired with the
        # tracker that records actuals). A default in-memory store makes
        # the second run of a repeated query history-informed even
        # without explicit wiring; PRESTO_TPU_HBO_CACHE persists it.
        self.history = (history if history is not None
                        else HistoryStore(default_history_path()))
        self.last_hbo = {"hits": 0, "misses": 0}
        self.last_join_reorders = 0
        self.session_properties = dict(session_properties or {})
        # admission control (reference: InternalResourceGroupManager
        # gating DispatchManager.createQueryInternal)
        self.resource_groups = resource_groups or ResourceGroupManager()
        # discovery-driven membership (reference: DiscoveryNodeManager):
        # workers that announce to `discovery` join the schedulable set
        # alongside the statically started ones.
        self.discovery = discovery
        self.cache_config = cache_config
        # concurrent-exchange knobs: the coordinator's own root collect
        # AND every worker's upstream pulls share one config
        self.exchange_config = (exchange_config
                                if exchange_config is not None
                                else DEFAULT_EXCHANGE)
        # spooled exchange (retry_policy=TASK): the coordinator opens
        # the shared spool base FIRST (sweeping orphans when attaching
        # to an existing base), then hands every worker a config
        # pointing at the SAME directory — the local-FS stand-in for
        # disaggregated storage (Presto@Meta VLDB'23 §3)
        scfg = spool_config if spool_config is not None else DEFAULT_SPOOL
        task_retry = str(self.session_properties.get(
            "retry_policy", "")).strip().upper() == "TASK"
        self.spool = None
        self.spool_config = scfg
        if scfg.enabled or task_retry:
            from presto_tpu.spool.store import SpoolStore
            self.spool = SpoolStore(_dc.replace(scfg, enabled=True))
            self.spool_config = _dc.replace(
                scfg, enabled=True, base_dir=self.spool.base_dir,
                sweep_on_start=False)
        # worker memory arbitration (exec/memory.py): every in-process
        # worker gets a real MemoryPool sized from MemoryConfig; the
        # coordinator holds the cluster view over those pools for the
        # low-memory killer, and gossips per-query reservations to
        # admission on the heartbeat path
        from presto_tpu.config import DEFAULT_MEMORY
        mcfg = memory_config if memory_config is not None \
            else DEFAULT_MEMORY
        self.memory_config = mcfg
        # cluster mesh tier (server/mesh_tier.py): one config governs
        # the coordinator's co-location policy AND every in-process
        # worker's slice advertisement
        from presto_tpu.config import DEFAULT_MESH_TIER
        self.mesh_config = (mesh_config if mesh_config is not None
                            else DEFAULT_MESH_TIER)
        self.last_cluster_mesh = None
        self.workers: List[TpuWorkerServer] = [
            TpuWorkerServer(connector, node_id=f"tpu-worker-{i}",
                            shared_secret=shared_secret,
                            cache_config=cache_config,
                            spool_config=self.spool_config,
                            exchange_config=exchange_config,
                            memory_config=memory_config,
                            mesh_config=self.mesh_config).start()
            for i in range(n_workers)]
        self.cluster_memory = None
        if mcfg.pool_bytes:
            from presto_tpu.exec.memory import ClusterMemoryManager
            pools = [w.task_manager.memory_pool for w in self.workers
                     if w.task_manager.memory_pool is not None]
            if pools:
                self.cluster_memory = ClusterMemoryManager(
                    pools,
                    budget_bytes=mcfg.cluster_budget(len(self.workers)))
        # heartbeat-gossiped cluster reservations ({qid: bytes} summed
        # over worker pools) — consumed by resource-group memory quotas
        self.cluster_reservations: Dict[str, int] = {}
        attach = getattr(self.resource_groups,
                         "attach_cluster_reservations", None)
        if attach is not None:
            attach(lambda: dict(self.cluster_reservations))
        # cache-affinity placement memory (reference: the coordinator's
        # fragment-result-cache-aware NetworkLocationCache / soft
        # affinity SplitPlacementPolicy): remembers which worker holds a
        # fragment fingerprint so repeat queries land on the warm cache
        self.affinity = AffinityRouter()
        self.all_worker_uris = [f"http://127.0.0.1:{w.port}"
                                for w in self.workers]
        self._inprocess_uris = frozenset(self.all_worker_uris)
        self.dead: set = set()
        # graceful-decommission set: workers that reported SHUTTING_DOWN
        # (or answered a task POST with the draining 410). They leave
        # the schedulable set WITHOUT a breaker penalty; their running
        # tasks finish and their committed spools stay readable.
        self.drained: set = set()
        # THE membership lock: every read of the schedulable set and
        # every dead/drained mutation flows through _membership() under
        # this lock (membership-chokepoint rule) so a failure-detector
        # sweep can never interleave with a scheduler's placement
        # snapshot and observe half-applied state
        self._membership_lock = threading.Lock()
        self._members_seen: set = set(self.all_worker_uris)
        self.membership_stats = {"joins": 0, "departures": 0,
                                 "drains": 0}
        # this cluster's fault-tolerant RPC chokepoint: per-worker
        # circuit breakers + per-request-class retry policies; chaos
        # tests install a FaultInjector on it
        self.http = HttpClient(config=transport_config)
        self._query_counter = 0
        self._lock = threading.Lock()
        self._plans: Dict[str, PlanNode] = {}
        # materialized views (presto_tpu/mv/): manager is lazy — built
        # on the first MV statement — so query-only clusters pay
        # nothing; a journal path makes definitions restart-durable
        self.mv_config = mv_config
        self.mv_journal_path = mv_journal_path
        self._mv_manager = None
        # introspection plane: system tables can now see this cluster;
        # the wide-event JSONL sink registers (a no-op without a
        # configured path) and the sampling profiler starts
        connector.attach_cluster(self)
        from presto_tpu.obs.profiler import PROFILER
        from presto_tpu.obs.wide_events import install_event_log_sink
        install_event_log_sink()
        PROFILER.ensure_started()
        # telemetry history + alerting (obs/tsdb.py, obs/alerts.py):
        # the scraper rides check_workers' heartbeat cadence — every
        # sweep snapshots the coordinator registry plus each live
        # worker's /v1/metrics into the TSDB, then the alert engine
        # evaluates its catalog against the history just written
        from presto_tpu.config import DEFAULT_OBS
        from presto_tpu.obs.alerts import AlertEngine
        from presto_tpu.obs.tsdb import Telemetry
        self.obs_config = (obs_config if obs_config is not None
                           else DEFAULT_OBS)
        self.telemetry = Telemetry(self.obs_config)
        self.alerts = AlertEngine(self.telemetry.store,
                                  config=self.obs_config)
        # first history point at t=0 via one real probe round: the
        # probes dial the client pool, so the coordinator's transport
        # series exist BEFORE the first query and its bracket pair can
        # show the query's delta (a bare local sweep here would miss
        # every counter that is born on first use)
        self.check_workers()

    @property
    def worker_uris(self) -> List[str]:
        return self._membership()

    def _membership(self, dead_add=(), dead_remove=(), drained_add=(),
                    drained_remove=()) -> List[str]:
        """THE membership chokepoint (membership-chokepoint rule):
        every read of the schedulable worker set and every mutation of
        the dead/drained sets happens inside this one lock. Callers
        collect probe verdicts FIRST (RPCs never run under the lock)
        and apply them here in one shot, so scheduling snapshots always
        see a consistent membership state. Returns the live URI list:
        static workers plus fresh discovery announcements, minus dead
        and draining nodes."""
        with self._membership_lock:
            for u in dead_add:
                if u not in self.dead:
                    # lint: disable=membership-chokepoint
                    self.dead.add(u)
                    self.membership_stats["departures"] += 1
                    _M_MEMBER_DEPARTURES.inc()
            for u in dead_remove:
                if u in self.dead:
                    # lint: disable=membership-chokepoint
                    self.dead.discard(u)
                    self.membership_stats["joins"] += 1
                    _M_MEMBER_JOINS.inc()
            for u in drained_add:
                if u not in self.drained:
                    # lint: disable=membership-chokepoint
                    self.drained.add(u)
                    self.membership_stats["drains"] += 1
                    _M_MEMBER_DRAINS.inc()
            for u in drained_remove:
                if u in self.drained:
                    # lint: disable=membership-chokepoint
                    self.drained.discard(u)
                    self.membership_stats["joins"] += 1
                    _M_MEMBER_JOINS.inc()
            uris = list(self.all_worker_uris)
            if self.discovery is not None:
                uris += [u for u in self.discovery.active_workers()
                         if u not in uris]
            # forget dead/drained entries that are neither static nor
            # announced: they cannot re-enter placement without a fresh
            # announcement, which re-evaluates them anyway — without
            # this, continuous churn grows the sets without bound
            known = set(uris)
            for u in [u for u in self.dead if u not in known]:
                # lint: disable=membership-chokepoint
                self.dead.discard(u)
            for u in [u for u in self.drained if u not in known]:
                # lint: disable=membership-chokepoint
                self.drained.discard(u)
            live = [u for u in uris if u not in self.dead
                    and u not in self.drained]
            for u in live:
                if u not in self._members_seen:
                    self._members_seen.add(u)
                    self.membership_stats["joins"] += 1
                    _M_MEMBER_JOINS.inc()
            return live

    def _probe_candidates(self) -> List[str]:
        """Every URI the failure detector should probe: static workers,
        fresh discovery announcements, and currently dead/drained nodes
        (the re-admission path needs to see them answer again). Built
        under the membership lock; the probes themselves run outside."""
        with self._membership_lock:
            uris = list(self.all_worker_uris)
            if self.discovery is not None:
                uris += [u for u in self.discovery.active_workers()
                         if u not in uris]
            uris += [u for u in sorted(self.dead) if u not in uris]
            uris += [u for u in sorted(self.drained) if u not in uris]
            return uris

    def membership_snapshot(self) -> dict:
        """Locked point-in-time membership view (EXPLAIN ANALYZE's
        "Membership:" line and status surfaces)."""
        live = self._membership()
        with self._membership_lock:
            return {"live": len(live), "dead": len(self.dead),
                    "drained": len(self.drained),
                    **self.membership_stats}

    # ---------------------------------------------------- failure detector
    def check_workers(self) -> List[str]:
        """Active liveness probe (reference:
        failureDetector/HeartbeatFailureDetector.java:76 + the
        discovery-announcement timeout in DiscoveryNodeManager): probe
        /v1/info/state so one sweep yields both verdicts — unreachable
        workers are marked dead so the scheduler stops placing tasks on
        them (and RE-ADMITTED when they answer again), and workers
        reporting SHUTTING_DOWN move to the drained set while their
        running tasks finish and their spools stay readable. Dead
        workers keep being probed through the circuit breaker: while
        its breaker is OPEN the probe fast-fails without touching the
        network; once the cooldown elapses the half-open state lets
        exactly one real probe through, and a restarted worker rejoins
        the schedulable set instead of staying banned forever. All
        verdicts are applied through the single locked membership
        chokepoint; the probe RPCs run outside it. Returns the live
        URI list."""
        dead_add: List[str] = []
        dead_remove: List[str] = []
        drained_add: List[str] = []
        drained_remove: List[str] = []
        for uri in self._probe_candidates():
            try:
                state = self.http.get_json(f"{uri}/v1/info/state",
                                           request_class="probe")
            except Exception:     # noqa: BLE001 — any failure = dead node
                dead_add.append(uri)
                continue
            if str(state).upper() == "SHUTTING_DOWN":
                drained_add.append(uri)
                dead_remove.append(uri)
            else:
                if uri in self.dead:
                    log.info("worker %s recovered; re-admitting", uri)
                dead_remove.append(uri)
                drained_remove.append(uri)
        live = self._membership(
            dead_add=dead_add, dead_remove=dead_remove,
            drained_add=drained_add, drained_remove=drained_remove)
        if self.memory_config.pool_bytes:
            self._scrape_memory(live)
        self._scrape_telemetry(live)
        return live

    def _scrape_memory(self, live: List[str]) -> None:
        """Heartbeat-path memory gossip: pull every live worker's
        /v1/memory pool snapshot and aggregate per-query reservations
        into the cluster view that admission quotas consult. A failed
        scrape keeps the previous view — stale beats empty (an empty
        view would wave oversized queries through)."""
        agg: Dict[str, int] = {}
        ok = False
        for uri in live:
            try:
                mem = self.http.get_json(f"{uri}/v1/memory",
                                         request_class="probe")
            except Exception:   # noqa: BLE001 — dead node, next sweep
                continue
            ok = True
            by_query = (mem.get("memoryPool") or {}).get(
                "queryReservations") or {}
            for qid, b in by_query.items():
                agg[qid] = agg.get(qid, 0) + int(b)
        if ok or not live:
            self.cluster_reservations = agg

    def _scrape_telemetry(self, live: List[str],
                          force: bool = False) -> None:
        """Heartbeat-path telemetry sweep: coordinator registry plus
        every live worker's /v1/metrics into the history store, then
        one alert-evaluation round over what was just written. The
        scraper self-throttles (sweep spacing + overhead budget) and
        never raises — history is advisory, probing is not. `force`
        (the query brackets) bypasses the spacing throttle; bracket
        callers pass no workers, so a forced sweep never adds
        per-query worker HTTP fetches."""
        try:
            swept = self.telemetry.scrape(
                workers=live,
                fetch=lambda uri: self.http.request(
                    f"{uri}/v1/metrics",
                    request_class="probe").body.decode(
                        "utf-8", "replace"),
                force=force)
            if swept:
                self.alerts.evaluate()
        except Exception:   # noqa: BLE001 — advisory plane only
            log.exception("telemetry sweep failed; continuing")

    def decommission(self, worker_uri: str,
                     timeout_s: Optional[float] = None) -> dict:
        """Gracefully drain one worker: PUT /v1/info/state
        "SHUTTING_DOWN" (the native worker's node-state shutdown
        protocol) and mark it drained through the membership
        chokepoint. The PUT blocks until the worker's running tasks
        finished and committed their spools (or its drain timeout
        elapsed), so on return the node holds no live work and new
        queries schedule around it. Returns the worker's drain
        report."""
        import json as _json
        from presto_tpu.config import DEFAULT_ELASTIC
        wait_s = (DEFAULT_ELASTIC.drain_timeout_s
                  if timeout_s is None else timeout_s)
        resp = self.http.request(
            f"{worker_uri}/v1/info/state", method="PUT",
            body=_json.dumps("SHUTTING_DOWN").encode(),
            headers={"Content-Type": "application/json"},
            request_class="control", timeout=wait_s + 10.0,
            attempts=1)
        self._membership(drained_add=[worker_uri])
        return resp.json()

    def start_heartbeat(self, interval_s: float = 5.0) -> "TpuCluster":
        """Periodic background liveness prober (reference:
        failureDetector/HeartbeatFailureDetector.java:76 — continuous
        monitoring, not only the on-failure probe): dead workers leave
        the schedulable set BEFORE the next query fails on them."""
        self._hb_stop = threading.Event()

        def loop():
            while not self._hb_stop.wait(interval_s):
                try:
                    self.check_workers()
                except Exception:   # noqa: BLE001 — prober must survive
                    log.exception(
                        "heartbeat probe sweep failed; continuing")

        self._hb_thread = spawn("coordinator", "heartbeat", loop)
        return self

    def stop(self):
        hb = getattr(self, "_hb_stop", None)
        if hb is not None:
            hb.set()
        if self._mv_manager is not None:
            self._mv_manager.stop_refresher()
        for w in self.workers:
            w.stop()
        if self.spool is not None:
            self.spool.close()

    def _task_retry(self) -> bool:
        """Is stage-level recovery (retry_policy=TASK) active for this
        cluster's queries? Requires the spool store — without spooled
        outputs there is nothing sound to recover from."""
        return self.spool is not None and str(
            self.session_properties.get("retry_policy", "")
        ).strip().upper() == "TASK"

    # ------------------------------------------------------------------
    def plan_sql(self, sql: str) -> PlanNode:
        from presto_tpu.sql.parser import parse_sql
        with TRACER.span(None, "plan", plan_cache_hit=sql in self._plans):
            if sql not in self._plans:
                self._plans[sql] = self.planner.plan_query(parse_sql(sql))
            return self._plans[sql]

    def execute_sql(self, sql: str,
                    _capture: bool = False,
                    cancel_event=None) -> List[tuple]:
        with self._lock:
            self._query_counter += 1
            qid = f"cluster_q{self._query_counter}"
        # one trace a statement: under the statement server the scope is
        # already open and this keeps it; called directly, the statement
        # starts here (the id is salted: cluster query ids repeat across
        # the clusters of one process, the tracer is process-wide)
        with root_scope(f"{qid}_{uuid.uuid4().hex[:8]}",
                        DEFAULT_OBS.sampled(random.random())):
            return self._execute_sql(qid, sql, _capture, cancel_event)

    def _execute_sql(self, qid: str, sql: str, _capture: bool,
                     cancel_event) -> List[tuple]:
        from presto_tpu.utils.tracing import query_lifecycle

        # plugin access control: the cluster is the network-exposed
        # entry point (statement server / DBAPI), so it must enforce the
        # security SPI exactly like LocalEngine
        from presto_tpu.spi import manager as _plugins
        user = self.session_properties.get("user", "")
        _plugins.check_can_execute(user, sql)
        _plugins.check_statement_access(
            user, sql,
            plan_full=lambda: self.plan_sql(sql),
            plan_query=self.planner.plan_query)

        # wide-event query log: exactly ONE event per cluster query id,
        # success or failure — recovery retries happen INSIDE the body,
        # so they can never duplicate it (obs/wide_events.py)
        from presto_tpu.obs import wide_events as _wide
        with TRACER.span(None, "telemetry"):
            pre = _wide.pre_query_snapshot(self)
            self._scrape_telemetry((), force=True)
        # bracket the query with LOCAL-ONLY telemetry sweeps so
        # metrics_history holds a before/after pair for every
        # coordinator-side counter the query moved (transport,
        # admission, memory) even when the background heartbeat is not
        # running; worker registries ride the heartbeat cadence —
        # fetching them here would add one HTTP round-trip per worker
        # to every query
        try:
            with query_lifecycle(qid, sql) as box:
                group = self.resource_groups.select(
                    user=self.session_properties.get("user", ""),
                    source=self.session_properties.get("source", ""))
                # when the statement front door already admitted this
                # query (dispatcher pool thread), acquire returns a no-op
                # nested slot — admission happens once per statement
                with (contextlib.nullcontext()
                      if current_admission() is not None
                      else TRACER.span(None, "admission_wait",
                                       group=group.path)):
                    slot = group.acquire(timeout_s=600, query_id=qid)
                self.last_admission = {
                    "group": slot.group.path,
                    "queue_wait_s": slot.queue_wait_s or 0.0}
                with slot:
                    head = (sql.lstrip().split(None, 1)[0].lower()
                            if sql.strip() else "")
                    if head == "explain":
                        from presto_tpu.plan.nodes import explain as _ex
                        rest = sql.lstrip()[len("explain"):].lstrip()
                        if rest.lower().startswith("analyze"):
                            text = self.explain_analyze_sql(
                                rest[len("analyze"):].lstrip())
                        else:
                            text = _ex(self.plan_sql(rest))
                        box[0] = [(line,) for line in text.splitlines()]
                    elif head in ("create", "insert", "drop",
                                  "delete", "refresh"):
                        box[0] = self._execute_write(sql)
                    else:
                        box[0] = self._execute_plan(
                            self.plan_sql(sql), capture=_capture,
                            cancel_event=cancel_event)
        except Exception as e:
            with TRACER.span(None, "telemetry"):
                _wide.emit_wide_event(self, qid, sql, rows=None,
                                      error=str(e), pre=pre)
            raise
        with TRACER.span(None, "telemetry"):
            _wide.emit_wide_event(self, qid, sql, rows=box[0], error=None,
                                  pre=pre)
            self._scrape_telemetry((), force=True)
        return box[0]

    @property
    def mv_manager(self):
        """Lazy materialized-view manager (presto_tpu/mv/). Refresh
        work executes through this cluster's own execute_sql, so
        admission, task-retry recovery and wide events all apply."""
        if self._mv_manager is None:
            from presto_tpu.config import DEFAULT_MV
            from presto_tpu.mv.manager import MaterializedViewManager
            self._mv_manager = MaterializedViewManager(
                self.connector, run_sql=self.execute_sql,
                groups=self.resource_groups,
                config=self.mv_config or DEFAULT_MV,
                journal_path=self.mv_journal_path)
        return self._mv_manager

    def consume_mv_event(self) -> Optional[dict]:
        """Pop the calling thread's pending refresh annotation for the
        wide-event `mv` block (obs/wide_events.py) — None for queries
        that did not refresh a materialized view."""
        mgr = self._mv_manager
        return mgr.consume_event() if mgr is not None else None

    def _execute_mv(self, stmt) -> List[tuple]:
        """CREATE/REFRESH/DROP MATERIALIZED VIEW — coordinator-side
        metadata ops plus (for REFRESH) delta/full queries dispatched
        through the normal distributed path."""
        from presto_tpu.mv.manager import MVError
        from presto_tpu.sql import ast as A
        from presto_tpu.sql.analyzer import AnalysisError

        try:
            if isinstance(stmt, A.CreateMaterializedView):
                self.mv_manager.create(stmt.name, stmt.sql,
                                       if_not_exists=stmt.if_not_exists)
                return [(0,)]
            if isinstance(stmt, A.RefreshMaterializedView):
                _kind, n = self.mv_manager.refresh(stmt.name)
                return [(n,)]
            self.mv_manager.drop(stmt.name, if_exists=stmt.if_exists)
            return [(0,)]
        except MVError as e:
            raise AnalysisError(str(e)) from e

    def _execute_write(self, sql: str) -> List[tuple]:
        """Distributed CTAS / INSERT ... SELECT: the coordinator runs the
        metadata DDL (CreateTableTask role), then schedules TableWriter
        fragments on the workers — each writes its partition of rows and
        reports a count; the coordinator sums them (TableFinish role).
        Literal-VALUES inserts and bare DDL run coordinator-side."""
        from presto_tpu.plan.nodes import TableWriterNode
        from presto_tpu.sql import ast as A
        from presto_tpu.sql.analyzer import AnalysisError
        from presto_tpu.sql.parser import parse_statement
        from presto_tpu.types import BIGINT

        stmt = parse_statement(sql)
        conn = self.connector
        if isinstance(stmt, (A.CreateMaterializedView,
                             A.RefreshMaterializedView,
                             A.DropMaterializedView)):
            return self._execute_mv(stmt)
        if not hasattr(conn, "create"):
            raise AnalysisError("connector is not writable")
        query = getattr(stmt, "query", None)
        if query is None:
            # bare DDL / literal VALUES: coordinator-local metadata ops
            from presto_tpu.exec.engine import LocalEngine
            return LocalEngine(conn).execute_sql(sql)

        plan = self.planner.plan_query(query)
        if isinstance(stmt, A.CreateTableAs):
            if stmt.if_not_exists and conn.exists(stmt.name):
                return [(0,)]
            conn.create(stmt.name, list(zip(plan.output_names,
                                            plan.output_types)))
        elif not conn.exists(stmt.name):
            raise AnalysisError(f"unknown table {stmt.name}")
        if getattr(stmt, "columns", None):
            # INSERT (col list): map SELECT outputs to the declared
            # columns, NULL-fill the rest — same semantics as
            # LocalEngine's literal path (engine.py INSERT handling)
            from presto_tpu.expr.nodes import InputRef, Literal
            from presto_tpu.plan.nodes import ProjectNode
            from presto_tpu.types import UNKNOWN
            schema = conn.schema(stmt.name)
            names = [c for c, _t in schema]
            unknown = [c for c in stmt.columns if c not in names]
            if unknown:
                raise AnalysisError(
                    f"INSERT columns not in table: {unknown}")
            if len(stmt.columns) != len(plan.output_types):
                raise AnalysisError(
                    f"INSERT arity {len(plan.output_types)} != column "
                    f"list {len(stmt.columns)}")
            pos = {c: i for i, c in enumerate(stmt.columns)}
            exprs, types = [], []
            for c, t in schema:
                if c in pos:
                    i = pos[c]
                    exprs.append(InputRef(i, plan.output_types[i]))
                    types.append(plan.output_types[i])
                else:
                    exprs.append(Literal(None, UNKNOWN))
                    types.append(t)
            plan = ProjectNode(tuple(names), tuple(types), plan,
                               tuple(exprs))
        schema = conn.schema(stmt.name)
        if not getattr(stmt, "columns", None) \
                and len(plan.output_types) != len(schema):
            raise AnalysisError(
                f"INSERT arity {len(plan.output_types)} != table "
                f"{len(schema)}")
        # positional semantics: the i-th SELECT output feeds the i-th
        # table column (the column-list case pre-projected to schema
        # order above)
        # Atomic commit (reference: TableFinishOperator + ConnectorPageSink
        # commit — writes become visible only when the whole query
        # succeeds). CTAS targets are freshly created, so drop-on-failure
        # already gives atomicity; INSERT into an existing table stages
        # the task writes into a temp table and moves them into the
        # target only after every fragment finished.
        is_insert = not isinstance(stmt, A.CreateTableAs)
        target = stmt.name
        if is_insert:
            import uuid
            target = f"stage_{uuid.uuid4().hex[:12]}_{stmt.name}"
            conn.create(target, list(schema))
        writer = TableWriterNode(("rows",), (BIGINT,), source=plan,
                                 table=target,
                                 column_names=tuple(
                                     c for c, _t in schema))
        # Scaled writers (reference: execution/scheduler/
        # ScaledWriterScheduler.java + SystemSessionProperties
        # scale_writers/writer_min_size): writer-task count scales with
        # the estimated data volume instead of always using every
        # worker — small inserts get one writer (no N tiny files /
        # per-task commit overhead), big ones fan out. The reference
        # scales at runtime on buffer backlog; with static shapes the
        # volume is estimable at plan time, so admission picks the
        # count up front.
        writer_tasks = None
        if (self.session_properties.get("scale_writers", "true")
                .lower() != "false"):
            try:
                from presto_tpu.exec.executor import _row_bytes
                from presto_tpu.plan.stats import estimate_rows
                est_rows = estimate_rows(plan, conn, self.history)
                min_size = int(self.session_properties.get(
                    "writer_min_size", 32 * 1024 * 1024))
                est_bytes = max(est_rows, 1) * _row_bytes(
                    plan.output_types)
                writer_tasks = max(
                    1, -(-est_bytes // max(min_size, 1)))
            except Exception:   # noqa: BLE001 — estimate is advisory
                writer_tasks = None
        try:
            # NON-idempotent: never auto-retried (a partial write on a
            # surviving worker would duplicate rows; reference: streaming
            # INSERT failures fail the query)
            counts = self._execute_plan_once(writer,
                                             writer_tasks=writer_tasks)
        except Exception:
            if is_insert:
                conn.drop(target, if_exists=True)      # discard the stage
            else:
                conn.drop(stmt.name, if_exists=True)   # no partial CTAS
            raise
        if is_insert:
            # commit: one locked raw-array move (exact decimals, no
            # python-value round trip); any connector without the fast
            # path takes the page route. The stage is always dropped.
            try:
                if hasattr(conn, "move_table_rows"):
                    conn.move_table_rows(target, stmt.name)
                else:
                    t = conn.table(target)
                    cap = max(int(t.num_rows), 1)
                    page = t.page(columns=[c for c, _t in schema],
                                  capacity=cap)
                    conn.append_rows(stmt.name, page.to_pylist())
            finally:
                conn.drop(target, if_exists=True)
        return [(sum(int(r[0]) for r in counts if r[0] is not None),)]

    def explain_analyze_sql(self, sql: str) -> str:
        """Execute, then render per-fragment / per-operator row counts
        from the workers' TaskInfo stats trees (the coordinator's
        EXPLAIN ANALYZE surface over the wire). Stats capture adds one
        TaskInfo GET per task, so it is gated to this entry point."""
        rows = self.execute_sql(sql, _capture=True)
        by_frag: Dict[int, Dict[str, list]] = {}
        for fid, info in getattr(self, "last_task_infos", []):
            stats = info.get("stats") or {}
            for pipe in stats.get("pipelines", []):
                for op in pipe.get("operatorSummaries", []):
                    key = (op.get("planNodeId"), op.get("operatorType"))
                    agg = by_frag.setdefault(fid, {}).setdefault(
                        key, [0, 0, None])
                    agg[0] += int(op.get("outputPositions", 0))
                    agg[1] += 1
                    agg[2] = agg[2] or op.get("canonicalKey")
        lines = [f"EXPLAIN ANALYZE ({len(rows)} result rows)"]
        for fid in sorted(by_frag):
            lines.append(f"Fragment {fid}:")
            for (nid, op_type), (total, ntasks, ckey) in sorted(
                    by_frag[fid].items()):
                # estimates vs actuals: the history entry for this
                # operator's canonical subtree is what the NEXT planning
                # of an equivalent node will estimate
                known = (self.history.rows.get(ckey)
                         if self.history is not None and ckey else None)
                est = f"est_rows={int(known)} " if known is not None \
                    else ""
                lines.append(
                    f"  {op_type} [node {nid}]: {est}{total} rows "
                    f"across {ntasks} task(s)")
        cache_line = self._render_cache_stats(
            getattr(self, "last_task_infos", []))
        if cache_line:
            lines.append(cache_line)
        ex = getattr(self, "last_exchange_stats", None)
        if ex is not None:
            lines.append(
                f"Exchange: fetches={ex['fetches']} "
                f"pages={ex['pages']} bytes={ex['bytes']} "
                f"truncations={ex['truncations']} "
                f"buffered_bytes_hw={ex['buffered_bytes_high_water']} "
                f"buffer_depth_hw={ex['buffer_depth_high_water']}")
        cmesh = getattr(self, "last_cluster_mesh", None)
        if cmesh is not None:
            lines.append(
                f"Mesh: cluster=true worker={cmesh['worker']} "
                f"group={cmesh['group']} ndev={cmesh['ndev']} "
                f"colocated_stages={cmesh['colocated_stages']} "
                f"ici_bytes={cmesh['ici_bytes']} "
                f"fallbacks={cmesh['fallbacks']}")
        spool = getattr(self, "last_spool_stats", None)
        if spool is not None:
            lines.append(
                f"Spool: commits={spool['commits']} "
                f"bytes={spool['bytes_written']} "
                f"recoveries={spool['recoveries']} "
                f"fallback_reads={spool['fallback_reads']} "
                f"gc={spool['gc']}")
        adm = getattr(self, "last_admission", None)
        if adm is not None:
            lines.append(
                f"Admission: group={adm['group']} "
                f"queue_wait={adm['queue_wait_s']:.3f}s")
        if self.cluster_memory is not None:
            cm = self.cluster_memory
            pools = cm.pools
            lines.append(
                f"Memory: reserved={cm.cluster_reserved()} "
                f"budget={cm.cluster_budget()} "
                f"revocations={sum(p.revocations for p in pools)} "
                f"revoked_bytes={sum(p.revoked_bytes for p in pools)} "
                f"kills={cm.kills}")
        mem = getattr(self, "last_membership", None)
        if mem is not None:
            lines.append(
                f"Membership: live={mem['live']} dead={mem['dead']} "
                f"drained={mem['drained']} joins={mem['joins']} "
                f"departures={mem['departures']} "
                f"drains={mem['drains']}")
        hbo = getattr(self, "last_hbo", None) or {}
        df_pruned = sum(
            int((((info.get("stats") or {}).get("runtimeStats") or {})
                 .get("dynamicFilterRowsPruned") or {}).get("sum", 0))
            for _fid, info in getattr(self, "last_task_infos", []))
        lines.append(
            f"HBO: hits={hbo.get('hits', 0)} "
            f"misses={hbo.get('misses', 0)} "
            f"join_reorders={getattr(self, 'last_join_reorders', 0)} "
            f"dynamic_filter_rows_pruned={df_pruned}")
        from presto_tpu.obs.profiler import PROFILER
        ps = PROFILER.stats()
        lines.append(
            f"Profile: samples={ps['samples']} buckets={ps['buckets']} "
            f"overhead={PROFILER.overhead_fraction() * 100:.2f}%")
        trace = self.render_trace()
        if trace:
            lines.append(
                f"Trace {getattr(self, 'last_trace_id', '')}:")
            lines.extend("  " + ln for ln in trace.splitlines())
        return "\n".join(lines)

    # ---------------------------------------------------------- tracing
    def _scrape_worker_traces(self, trace_id: str) -> None:
        """GET /v1/trace/{id} from every worker of another process and
        stitch the spans into the coordinator tracer. In-process workers
        share the process tracer: their spans are already there."""
        remote = [u for u in self.worker_uris
                  if u not in self._inprocess_uris]
        if not remote:
            return
        with TRACER.span(trace_id, "telemetry", workers=len(remote)):
            for uri in remote:
                try:
                    doc = self.http.get_json(
                        f"{uri}/v1/trace/{trace_id}",
                        request_class="control")
                    TRACER.merge_remote(trace_id, doc)
                except Exception:   # noqa: BLE001 — best-effort
                    log.debug("trace scrape failed for %s", uri,
                              exc_info=True)

    def render_trace(self, query_id: Optional[str] = None) -> str:
        """One cross-node timeline for `query_id` (default: the most
        recent sampled query) — coordinator and worker spans under the
        same query trace id, sorted by start time."""
        qid = query_id or getattr(self, "last_trace_id", None)
        return TRACER.render(qid) if qid else ""

    @staticmethod
    def _render_cache_stats(infos) -> str:
        """Roll the workers' fragmentResultCache* runtime metrics up to
        one EXPLAIN ANALYZE line (reference: FragmentCacheStats surfaced
        through the native worker's runtime metrics). Per-task snapshots
        repeat their worker's process-wide counters, so store counters
        dedupe by worker (latest snapshot wins) while per-task hit flags
        sum directly."""
        per_worker: Dict[str, dict] = {}
        task_hits = 0
        cached_tasks = 0
        for _fid, info in infos:
            rt = (info.get("stats") or {}).get("runtimeStats") or {}
            if "fragmentResultCacheHitCount" not in rt:
                continue
            cached_tasks += 1
            task_hits += int(
                (rt.get("fragmentResultCacheHit") or {}).get("sum", 0))
            uri = str((info.get("taskStatus") or {}).get("self", ""))
            per_worker[uri.split("/v1/", 1)[0]] = rt
        if not per_worker:
            return ""

        def total(name: str) -> int:
            return sum(int((rt.get(name) or {}).get("sum", 0))
                       for rt in per_worker.values())

        return (f"Result cache: {task_hits}/{cached_tasks} tasks served "
                f"from cache; store hits={total('fragmentResultCacheHitCount')} "
                f"misses={total('fragmentResultCacheMissCount')} "
                f"evictions={total('fragmentResultCacheEvictionCount')} "
                f"bytes={total('fragmentResultCacheSizeBytes')}")

    def _execute_plan(self, plan: PlanNode, _retried: bool = False,
                      capture: bool = False,
                      cancel_event=None) -> List[tuple]:
        """Streaming-mode recovery (reference: a worker failure fails the
        query; the dispatcher retries on the surviving nodes once the
        failure detector excludes the dead worker)."""
        try:
            return self._execute_plan_once(plan, capture=capture,
                                           cancel_event=cancel_event)
        except ClusterMemoryKillError:
            raise                   # terminal: killed queries never retry
        except (ClusterQueryError, OSError) as e:
            if cancel_event is not None and cancel_event.is_set():
                raise
            before = set(self.worker_uris)
            alive = set(self.check_workers())
            if _retried or alive == before or not alive:
                if isinstance(e, ClusterQueryError):
                    raise
                # terminal transport failure: surface the query-level
                # contract (clean ClusterQueryError, cause chained) —
                # callers never see raw socket errors
                raise ClusterQueryError(
                    f"query failed on transport error: {e}") from e
            return self._execute_plan(plan, _retried=True,
                                      capture=capture,
                                      cancel_event=cancel_event)

    def _execute_plan_once(self, plan: PlanNode,
                           capture: bool = False,
                           cancel_event=None,
                           writer_tasks: Optional[int] = None
                           ) -> List[tuple]:
        # Uncorrelated scalar subqueries execute through the cluster
        # itself (recursively), not a local engine: distributed partial/
        # final aggregation orders float summation differently, and a
        # literal produced by a different pipeline would break exact
        # comparisons like Q15's total_revenue = (select max(...)).
        # (outside the `plan` span: each runs a `query` of its own)
        plan = _ClusterSubqueryExec(self)._resolve_subqueries(plan)
        with TRACER.span(None, "plan") as plan_span:
            plan, h0, frags, merge_keys, mesh_plan = \
                self._fragment_plan(plan, writer_tasks)
            plan_span.attributes["fragments"] = len(frags)
        try:
            return self._run_fragments(frags, list(plan.output_types),
                                       capture=capture,
                                       merge_keys=merge_keys,
                                       cancel_event=cancel_event,
                                       writer_tasks=writer_tasks,
                                       mesh_plan=mesh_plan)
        finally:
            # planning-time HBO consultation delta for this query
            # (EXPLAIN ANALYZE's "HBO:" line)
            if h0 is not None:
                self.last_hbo = {
                    "hits": self.history.hits - h0[0],
                    "misses": self.history.misses - h0[1]}
            else:
                self.last_hbo = {"hits": 0, "misses": 0}

    def _fragment_plan(self, plan: PlanNode, writer_tasks):
        """From the logical plan to the fragments to schedule: join
        reordering, exchanges, the cut into fragments, and the mesh
        tier's offer to fuse them."""
        from presto_tpu.config import PROPERTIES, Session
        known = {p.name for p in PROPERTIES}
        session = Session({k: v for k, v in
                           self.session_properties.items() if k in known})
        h0 = ((self.history.hits, self.history.misses)
              if self.history is not None else None)
        # history-first greedy join reordering (ReorderJoins): the
        # smaller estimated side becomes the hash build before the
        # exchange planner decides broadcast vs repartition on it
        self.last_join_reorders = 0
        if session["join_reordering_enabled"]:
            plan, self.last_join_reorders = reorder_joins(
                plan, self.connector, self.history)
        ex_plan, merge_keys = _derange(
            add_exchanges(_unshare(plan), self.connector, session,
                          self.history))
        frags = create_fragments(ex_plan)
        # cluster mesh tier (server/mesh_tier.py, THE ICI-vs-HTTP
        # chokepoint): an eligible multi-stage plan fuses into ONE
        # single-task fragment on a mesh worker — the worker re-plans
        # exchanges locally, so every cut that would have been an HTTP
        # page pull lowers to an ICI collective. None keeps the HTTP
        # path byte-for-byte.
        mesh_plan = None
        if writer_tasks is None:
            from presto_tpu.server.mesh_tier import plan_cluster_mesh
            mesh_plan = plan_cluster_mesh(self, plan, len(frags))
        if mesh_plan is not None:
            from presto_tpu.plan.fragment import PlanFragment
            frags = [PlanFragment(0, _unshare(plan),
                                  Partitioning.SINGLE, ())]
            merge_keys = None
        return plan, h0, frags, merge_keys, mesh_plan

    # ------------------------------------------------------------------
    def _run_fragments(self, frags, out_types,
                       capture: bool = False, merge_keys=None,
                       writer_tasks: Optional[int] = None,
                       cancel_event=None, mesh_plan=None) -> List[tuple]:
        with self._lock:
            self._query_counter += 1
            qid = f"q{self._query_counter}_{int(time.time())}"
        run = functools.partial(
            self._run_fragments_scoped, qid, frags, out_types, capture,
            merge_keys, writer_tasks, cancel_event, mesh_plan)
        with root_scope(qid, DEFAULT_OBS.sampled(random.random())) as ctx:
            if ctx is None:
                return run()
            # sampled query: the coordinator opens the root span, the
            # trace_scope makes every RPC this scheduling thread issues
            # carry X-Presto-Trace with the root span as parent, and
            # span dumps of workers in other processes are scraped back
            # at query end into one stitched timeline
            self.last_trace_id = ctx.trace_id
            with TRACER.span(ctx.trace_id, "query", worker="coordinator",
                             fragments=len(frags)) as root:
                with trace_scope(ctx.trace_id, root.span_id):
                    rows = run()
            self._scrape_worker_traces(ctx.trace_id)
            return rows

    def _run_fragments_scoped(self, qid, frags, out_types, capture,
                              merge_keys, writer_tasks, cancel_event,
                              mesh_plan) -> List[tuple]:
        by_id = {f.fragment_id: f for f in frags}

        consumers: Dict[int, List[int]] = {}
        for f in frags:
            for src in set(f.remote_sources):
                consumers.setdefault(src, []).append(f.fragment_id)
        for src, cons in consumers.items():
            if len(cons) > 1 and by_id[src].partitioning not in (
                    Partitioning.BROADCAST, Partitioning.SINGLE):
                raise NotImplementedError(
                    "partitioned producer shared by several consumer "
                    "fragments (CTE materialization boundary — planned)")

        # membership snapshot at query START fixes the task COUNTS (W)
        # for the whole query — buffer wiring and split assignment must
        # not shift once any stage is posted. PLACEMENT, by contrast,
        # re-snapshots per stage (see schedule()) so mid-query joins and
        # drains are visible to every not-yet-scheduled stage.
        placement = list(self.worker_uris)
        W = len(placement)
        self.last_membership = self.membership_snapshot()
        with TRACER.span(None, "schedule", stages=len(frags)):
            specs = {f.fragment_id: fragment_to_protocol(f, self.connector)
                     for f in frags}

        stages: Dict[int, _Stage] = {}

        # hash_partition_count (SystemSessionProperties.
        # HASH_PARTITION_COUNT): tasks per hash-partitioned intermediate
        # stage; 0 = one per worker
        hpc = 0
        try:
            hpc = int(float(self.session_properties.get(
                "hash_partition_count", 0) or 0))
        except (TypeError, ValueError):
            hpc = 0

        def n_tasks(fid: int) -> int:
            spec = specs[fid]
            if mesh_plan is not None:
                # the fused cluster-mesh plan runs as ONE task on the
                # chosen mesh worker — parallelism comes from the mesh
                # devices inside the program, not from task fan-out
                return 1
            if fid == 0 and writer_tasks is not None \
                    and spec.scan_nodes:
                # scaled writers: a SOURCE-partitioned (scan-fed)
                # writer fragment's parallelism follows the estimated
                # data volume; gathered shapes (SINGLE producers under
                # the writer) keep the plan-driven count
                self.last_writer_tasks = max(
                    1, min(int(writer_tasks), W))
                return self.last_writer_tasks
            if spec.scan_nodes:
                return W
            for pfid in spec.remote_nodes.values():
                if by_id[pfid].partitioning == Partitioning.HASH:
                    return hpc if hpc > 0 else W
            return 1

        for f in frags:
            cons = consumers.get(f.fragment_id, [])
            part = f.partitioning
            offsets: Dict[int, int] = {}
            nbuf = 0
            for c in cons:
                offsets[c] = nbuf
                if part == Partitioning.SINGLE and n_tasks(c) > 1:
                    # One buffer would be drained destructively by N
                    # consumer tasks, silently splitting the stream —
                    # needs per-task buffers + broadcast like _emit_output
                    # does for multi-buffer SINGLE.
                    raise NotImplementedError(
                        "SINGLE-partitioned producer feeding a "
                        f"multi-task consumer fragment {c}")
                nbuf += 1 if part == Partitioning.SINGLE else n_tasks(c)
            nbuf = max(nbuf, 1)
            stages[f.fragment_id] = _Stage(
                specs[f.fragment_id], n_tasks(f.fragment_id), nbuf,
                offsets)

        if mesh_plan is not None:
            stages[0].mesh_worker = mesh_plan["worker"]
            stages[0].mesh_descriptor = mesh_plan["descriptor"]

        self._plan_dynamic_filters(stages, by_id)

        # leaf-first scheduling (children before parents so producer task
        # locations exist when consumers are created); dynamic-filter
        # build stages go before their siblings so a probe stage's
        # bounded wait overlaps the build actually running
        scheduled = set()

        def schedule(fid: int):
            if fid in scheduled:
                return
            srcs = list(dict.fromkeys(by_id[fid].remote_sources))
            srcs.sort(key=lambda s:
                      0 if stages[s].df_publish_channel is not None
                      else 1)
            for src in srcs:
                schedule(src)
            # per-STAGE placement snapshot (mid-query join): a worker
            # that announced after the query started is schedulable for
            # every stage not yet placed, and one that began draining
            # stops receiving new stages — while task counts stay
            # pinned to the query-start W so buffer wiring never shifts
            # under running stages
            stage_placement = self.worker_uris or placement
            self._start_stage(qid, fid, stages, by_id, stage_placement)
            scheduled.add(fid)

        def schedule_all():
            with TRACER.span(None, "schedule", stages=len(stages),
                             tasks=sum(st.n_tasks
                                       for st in stages.values())):
                schedule(0)

        def await_all():
            with TRACER.span(None, "await_tasks"):
                self._await_all(stages, cancel_event=cancel_event,
                                query_id=qid)

        batch_mode = (str(self.session_properties.get(
            "exchange_materialization_enabled", ""))
            .strip().lower() == "true")

        #: bound on spool-recovery rounds per query — each round needs a
        #: fresh worker death to do anything, so this never limits a
        #: single-fault run; it stops a flapping cluster from spinning
        MAX_RECOVERY_ROUNDS = 5

        self.last_recovery_events = []
        spool_before = None
        if self.spool is not None:
            from presto_tpu.spool.store import spool_counters
            spool_before = spool_counters()
        # exchange activity this query: counter deltas (process-global
        # registry, so in-process workers' pulls are included) plus the
        # absolute high-water gauges
        exchange_before = exchange_counters()
        # cluster-mesh activity bracket (same process-global-registry
        # assumption): ICI exchange bytes + fallback deltas
        from presto_tpu.server import mesh_tier as _mesh_tier
        mesh_ici_before = _mesh_tier.ici_bytes_total()
        mesh_fb_before = _mesh_tier.fallbacks_total()

        def run_query() -> List[tuple]:
            try:
                if batch_mode:
                    return self._run_fragments_batch(
                        qid, stages, by_id, placement, out_types,
                        merge_keys, capture, cancel_event)
                if self._task_retry():
                    # stage-level recoverable execution (retry_policy=
                    # TASK, Presto@Meta VLDB'23 §3): each failed await
                    # absorbs dead tasks from their committed spools /
                    # re-plans only the lost ones onto survivors, then
                    # awaits again — completed stages never re-run.
                    # Scheduling lives INSIDE the loop: a worker dying
                    # mid-schedule leaves partially-posted stages, and
                    # _recover_spooled's tail pass places the
                    # never-created tasks on survivors — it must never
                    # escape to the whole-query-retry path.
                    rounds = 0
                    need_schedule = True
                    while True:
                        try:
                            if need_schedule:
                                schedule_all()
                                need_schedule = False
                            await_all()
                            break
                        except ClusterMemoryKillError:
                            # the low-memory killer is terminal: a
                            # killed query must never re-execute, even
                            # though its spools could replay
                            raise
                        except (ClusterQueryError, OSError):
                            # recovery finishes any partial scheduling
                            # itself; re-running schedule() would
                            # double-post the already-created tasks
                            need_schedule = False
                            if cancel_event is not None \
                                    and cancel_event.is_set():
                                raise
                            if rounds >= MAX_RECOVERY_ROUNDS \
                                    or not self._recover_spooled(
                                        qid, stages, by_id):
                                raise
                            rounds += 1
                else:
                    schedule_all()
                    try:
                        await_all()
                    except ClusterMemoryKillError:
                        raise       # terminal: killed queries never retry
                    except (ClusterQueryError, OSError):
                        if cancel_event is not None \
                                and cancel_event.is_set():
                            raise
                        # task-level recovery (reference: scheduler/
                        # group recoverable grouped execution,
                        # SystemSessionProperties
                        # recoverable_grouped_execution): for a
                        # single-stage query, re-run ONLY the tasks that
                        # lived on dead workers — their split assignment
                        # is deterministic, so exactly the lost
                        # lifespans re-run
                        if not self._recover_dead_tasks(qid, stages,
                                                        by_id):
                            raise
                        await_all()
                if capture or self.history is not None:
                    self._capture_task_infos(stages)
                    self._record_history(stages, by_id)
                return self._collect_root(stages[0], out_types,
                                          merge_keys)
            finally:
                self._cleanup(stages, qid)
                if spool_before is not None:
                    from presto_tpu.spool.store import spool_counters
                    after = spool_counters()
                    self.last_spool_stats = {
                        k: after[k] - spool_before[k]
                        for k in after}
                ex_after = exchange_counters()
                self.last_exchange_stats = {
                    k: (ex_after[k] - exchange_before[k]
                        if not k.endswith("high_water") else ex_after[k])
                    for k in ex_after}
                # post-query membership view: joins/drains that landed
                # DURING the query show up in EXPLAIN ANALYZE
                self.last_membership = self.membership_snapshot()
                # cluster-mesh outcome for EXPLAIN ANALYZE / wide event
                if mesh_plan is not None:
                    ici = (_mesh_tier.ici_bytes_total()
                           - mesh_ici_before)
                    colocated = (mesh_plan["descriptor"]
                                 ["colocated_stages"] if ici > 0 else 0)
                    self.last_cluster_mesh = {
                        "worker": mesh_plan["worker"],
                        "group": mesh_plan["group"],
                        "ndev": mesh_plan["ndev"],
                        "colocated_stages": colocated,
                        "ici_bytes": int(ici),
                        "fallbacks": int(_mesh_tier.fallbacks_total()
                                         - mesh_fb_before)}
                    _mesh_tier.set_colocation_gauge(colocated)
                else:
                    self.last_cluster_mesh = None
                    _mesh_tier.set_colocation_gauge(0)

        return run_query()

    def _run_fragments_batch(self, qid, stages, by_id, placement,
                             out_types, merge_keys, capture,
                             cancel_event) -> List[tuple]:
        """Materialized-exchange batch execution (reference:
        presto-spark-base's stage-by-stage mode over materialized
        shuffles, ShuffleWrite.cpp): stages run to COMPLETION in
        producer-first order — their output frames persist on disk and
        replay from token 0 (MaterializedClientBuffer) — and a stage
        lost to a worker death re-runs ALONE on the survivors (its
        consumers have not started, its producers' outputs are still
        replayable), instead of failing or retrying the whole query."""
        order: List[int] = []
        seen = set()

        def topo(fid: int):
            if fid in seen:
                return
            seen.add(fid)
            for src in by_id[fid].remote_sources:
                topo(src)
            order.append(fid)

        topo(0)
        live_placement = list(placement)
        for pos, fid in enumerate(order):
            for _attempt in range(2):
                try:
                    if _attempt == 0:
                        self._start_stage(qid, fid, stages, by_id,
                                          live_placement)
                    self._await_all({fid: stages[fid]},
                                    cancel_event=cancel_event,
                                    query_id=qid)
                    break
                except ClusterMemoryKillError:
                    raise           # terminal: killed queries never retry
                except (ClusterQueryError, OSError):
                    if cancel_event is not None \
                            and cancel_event.is_set():
                        raise
                    if _attempt:
                        raise
                    # a dead worker also takes the materialized outputs
                    # of COMPLETED upstream tasks it hosted: regenerate
                    # those first (their survivors return FINISHED
                    # immediately), then re-post the whole current
                    # stage so its split bindings see the new producer
                    # locations
                    alive = set(self.check_workers())
                    if not alive:
                        raise
                    recovered = False
                    for up in order[:pos]:
                        if self._reschedule_stage(qid, up, stages,
                                                  by_id):
                            recovered = True
                            self._await_all({up: stages[up]},
                                            cancel_event=cancel_event,
                                            query_id=qid)
                    if self._reschedule_stage(qid, fid, stages, by_id,
                                              force_all=recovered):
                        recovered = True
                    if not recovered:
                        raise
                    live_placement = [w for w in live_placement
                                      if w in alive] or live_placement
        if capture or self.history is not None:
            self._capture_task_infos(stages)
            self._record_history(stages, by_id)
        return self._collect_root(stages[0], out_types, merge_keys)

    def _recover_dead_tasks(self, qid: str, stages: Dict[int, _Stage],
                            by_id) -> bool:
        """Streaming-mode task recovery: only safe when every stage's
        output is still pullable, i.e. the single-fragment shape
        (consumers re-pull from token 0 of the replacement task);
        multi-stage streaming plans fall back to the whole-query
        retry. Returns True if recovery was performed."""
        if len(stages) != 1:
            return False
        return self._reschedule_stage(qid, 0, stages, by_id)

    def _recover_spooled(self, qid: str, stages: Dict[int, _Stage],
                         by_id) -> bool:
        """retry_policy=TASK recovery round (reference: Presto@Meta
        VLDB'23 §3 — spooled intermediate results make individual task
        retry sound). Producer-first over the stage DAG:

          - a dead worker's task whose spool COMMITTED is absorbed: the
            work is done, its output lives in disaggregated storage;
            consumers read it there (direct spool fallback, or any live
            worker's HTTP spool serving). It is never re-executed.
          - a dead worker's task with NO committed spool lost its work:
            re-plan exactly that task onto a survivor as attempt N+1
            (deterministic split assignment re-reads the same
            lifespans).
          - a live task that FAILED (typically its pull from the dead
            producer exhausted before the spool committed) re-plans the
            same way — its replacement's remote splits point at the
            producers' CURRENT locations.

        Returns True when anything changed (the caller awaits again);
        False means this error is not recoverable here."""
        from presto_tpu.spool.store import record_recovery

        # survivors keep MEMBERSHIP order (static fleet first, then
        # announced joiners in announce order): deterministic like a
        # sort, but a worker that announced mid-query slots into the
        # index the departed worker vacated instead of wherever its
        # ephemeral port happens to sort
        survivors = self.check_workers()
        alive = set(survivors)
        if not alive:
            return False
        order: List[int] = []
        seen: set = set()

        def topo(fid: int):
            if fid in seen:
                return
            seen.add(fid)
            for src in by_id[fid].remote_sources:
                topo(src)
            order.append(fid)

        for fid in stages:
            topo(fid)
        changed = False
        for fid in order:
            stage = stages[fid]
            for t, uri in enumerate(list(stage.task_uris)):
                if t in stage.spool_done:
                    continue
                worker = uri.split("/v1/task/")[0]
                if worker not in alive:
                    committed = self.spool.find_committed_for_task(
                        stage.task_ids[t])
                    if committed is not None:
                        stage.spool_done.add(t)
                        stage.spool_task_ids[t] = committed.task_id
                        record_recovery("absorb")
                        self.last_recovery_events.append(
                            ("spool", fid, t))
                        log.info("task %s absorbed from committed "
                                 "spool %s", stage.task_ids[t],
                                 committed.path)
                        changed = True
                        continue
                    new_worker = survivors[t % len(survivors)]
                else:
                    # live worker: only a FAILED task needs re-planning
                    # (RUNNING consumers of a dead producer recover by
                    # themselves through the spool fallback)
                    try:
                        st = self.http.get_json(
                            f"{uri}/status",
                            headers={"X-Presto-Max-Wait": "0s"},
                            request_class="status_poll")
                    except OSError:
                        continue      # transient; next round retries
                    if st.get("state") != "FAILED":
                        continue
                    try:
                        self.http.delete(uri)
                    except Exception:   # noqa: BLE001 — best effort
                        pass
                    new_worker = worker
                attempt = int(stage.task_ids[t].rsplit(".", 1)[1]) + 1
                task_id, new_uri = self._post_stage_task(
                    qid, fid, stages, by_id, new_worker, t, attempt)
                stage.task_ids[t] = task_id
                stage.task_uris[t] = new_uri
                stage.recovered_tasks += 1
                record_recovery("retask")
                self.last_recovery_events.append(("retask", fid, t))
                log.info("task re-planned as %s on %s", task_id,
                         new_worker)
                changed = True
            # a scheduling-time death can leave the stage partially
            # posted: place the never-created tasks on survivors
            for t in range(len(stage.task_uris), stage.n_tasks):
                task_id, new_uri = self._post_stage_task(
                    qid, fid, stages, by_id,
                    survivors[t % len(survivors)], t, attempt=1)
                stage.task_ids.append(task_id)
                stage.task_uris.append(new_uri)
                stage.recovered_tasks += 1
                record_recovery("retask")
                self.last_recovery_events.append(("retask", fid, t))
                changed = True
            self.last_recovered_tasks = stage.recovered_tasks
        return changed

    def _reschedule_stage(self, qid: str, fid: int,
                          stages: Dict[int, _Stage], by_id,
                          force_all: bool = False) -> bool:
        """Re-post fragment `fid`'s tasks stranded on dead workers to
        survivors with bumped attempt ids (deterministic split
        assignment -> exactly the lost work re-runs). `force_all`
        re-posts EVERY task — needed when upstream producers moved and
        surviving tasks' remote splits still point at the old
        locations (batch-mode recovery)."""
        survivors = self.check_workers()   # membership order, as above
        alive = set(survivors)
        if not alive:
            return False
        stage = stages[fid]
        recovered = False
        for t, uri in enumerate(list(stage.task_uris)):
            worker = uri.split("/v1/task/")[0]
            if worker in alive and not force_all:
                continue
            attempt = int(stage.task_ids[t].rsplit(".", 1)[1]) + 1
            new_worker = (worker if worker in alive
                          else survivors[t % len(survivors)])
            task_id, new_uri = self._post_stage_task(
                qid, fid, stages, by_id, new_worker, t, attempt)
            stage.task_ids[t] = task_id
            stage.task_uris[t] = new_uri
            stage.recovered_tasks += 1
            recovered = True
        # a scheduling-time death can leave the stage partially posted:
        # place the never-created tasks on survivors
        for t in range(len(stage.task_uris), stage.n_tasks):
            task_id, new_uri = self._post_stage_task(
                qid, fid, stages, by_id, survivors[t % len(survivors)],
                t, attempt=1)
            stage.task_ids.append(task_id)
            stage.task_uris.append(new_uri)
            stage.recovered_tasks += 1
            recovered = True
        self.last_recovered_tasks = stage.recovered_tasks
        return recovered

    def _capture_task_infos(self, stages: Dict[int, _Stage]):
        """Fetch every task's TaskInfo (stats tree included) before
        cleanup deletes the tasks — the coordinator's QueryStats
        aggregation source (reference: per-task OperatorStats rolled up
        by SqlStageExecution)."""
        infos = []
        for fid, stage in stages.items():
            for uri in stage.task_uris:
                try:
                    infos.append((fid, self.http.get_json(uri)))
                except Exception:    # noqa: BLE001 — stats best-effort
                    pass
        self.last_task_infos = infos

    def _record_history(self, stages: Dict[int, _Stage], by_id) -> None:
        """Cluster-fed HBO: fold the workers' OBSERVED cardinalities
        back into the coordinator's HistoryStore at query end
        (reference: HistoryBasedPlanStatisticsTracker recording final
        QueryStats keyed by canonical plan hashes). Two granularities:
        per-operator summaries carry the worker-computed canonicalKey
        (local subtrees — scan/filter chains — hash identically to the
        planner's), and each fragment root is keyed by the
        coordinator-side digest of its engine subtree, which is what
        AddExchanges' est(build) consults for broadcast decisions."""
        if self.history is None:
            return
        per_op: Dict[tuple, int] = {}
        per_frag: Dict[int, int] = {}
        for fid, info in getattr(self, "last_task_infos", []):
            stats = info.get("stats") or {}
            per_frag[fid] = per_frag.get(fid, 0) + int(
                stats.get("outputPositions", 0) or 0)
            for pipe in stats.get("pipelines", []):
                for op in pipe.get("operatorSummaries", []):
                    key = op.get("canonicalKey")
                    if key:
                        k = (fid, str(op.get("planNodeId")), key)
                        per_op[k] = per_op.get(k, 0) + int(
                            op.get("outputPositions", 0) or 0)
        for (_fid, _nid, key), rows in per_op.items():
            self.history.record(key, rows)
        for fid, rows in per_frag.items():
            frag = by_id.get(fid)
            if frag is None:
                continue
            try:
                self.history.record(canonical_key(frag.root), rows)
            except Exception:  # noqa: BLE001 — feedback is best-effort
                pass
        try:
            self.history.save()
        except OSError:
            log.debug("HBO save failed", exc_info=True)

    # ----------------------------------------- cross-exchange dynamic filters
    def _plan_dynamic_filters(self, stages: Dict[int, _Stage],
                              by_id) -> None:
        """Decide, per query, which build stage publishes a join-key
        domain and which probe-side scan stage waits for it (reference:
        DynamicFilterService collecting build summaries and pushing
        TupleDomains into not-yet-scheduled probe splits). Eligibility:
        INNER/filtering-SEMI equi-join whose build side was cut into its
        own fragment, numeric key, and a build estimated small enough
        that waiting `dynamic_filter_wait_ms` is plausibly repaid."""
        from presto_tpu.config import PROPERTIES, Session
        from presto_tpu.plan import nodes as P
        from presto_tpu.expr.nodes import InputRef
        known = {p.name for p in PROPERTIES}
        session = Session({k: v for k, v in
                           self.session_properties.items() if k in known})
        if not session["dynamic_filtering_enabled"]:
            return
        wait_ms = int(session["dynamic_filter_wait_ms"])
        threshold = int(session["broadcast_join_threshold_rows"])

        def resolve(fid: int, node, ch: int):
            """Trace output channel `ch` of `node` (in fragment `fid`)
            back to a (fragment, table, column) scan origin, hopping
            exchange cuts into producer fragments."""
            if isinstance(node, P.TableScanNode):
                return (fid, node.table, node.columns[ch])
            if isinstance(node, P.FilterNode):
                return resolve(fid, node.source, ch)
            if isinstance(node, P.ProjectNode):
                e = node.expressions[ch]
                if isinstance(e, InputRef):
                    return resolve(fid, node.source, e.field)
                return None
            if isinstance(node, P.ExchangeNode):
                if node.source is not None:
                    return resolve(fid, node.source, ch)
                pfid = node.remote_fragment
                if pfid is None or pfid not in by_id:
                    return None
                return resolve(pfid, by_id[pfid].root, ch)
            if isinstance(node, P.JoinNode):
                if ch < len(node.probe.output_types):
                    return resolve(fid, node.probe, ch)
                return None
            if isinstance(node, P.AggregationNode):
                # group keys pass values through unchanged: filtering
                # the input on a key domain removes exactly the groups
                # that could not match
                if ch < len(node.group_fields):
                    return resolve(fid, node.source,
                                   node.group_fields[ch])
                return None
            return None

        def walk(n):
            yield n
            for c in n.children():
                if c is not None:
                    yield from walk(c)

        for fid in sorted(by_id):
            for node in walk(by_id[fid].root):
                if not isinstance(node, P.JoinNode) \
                        or not node.probe_keys:
                    continue
                if node.join_type not in (P.JoinType.INNER,
                                          P.JoinType.SEMI) \
                        or node.emit_flag:
                    continue
                build = node.build
                if not (isinstance(build, P.ExchangeNode)
                        and build.source is None
                        and build.remote_fragment in stages):
                    continue
                bfid = build.remote_fragment
                key_t = build.output_types[node.build_keys[0]]
                if key_t.is_string:
                    continue
                try:
                    est = estimate_rows(by_id[bfid].root,
                                        self.connector, self.history)
                except Exception:  # noqa: BLE001 — est gate is advisory
                    continue
                if est > threshold:
                    continue
                resolved = resolve(fid, node.probe,
                                   node.probe_keys[0])
                if resolved is None:
                    continue
                tfid, table, column = resolved
                target = stages.get(tfid)
                if target is None or target.df_spec is not None \
                        or stages[bfid].df_publish_channel is not None:
                    continue
                scan_ids = [nid for nid, tb in
                            target.spec.scan_nodes.items()
                            if tb == table]
                if len(scan_ids) != 1 or tfid == bfid:
                    continue
                stages[bfid].df_publish_channel = node.build_keys[0]
                target.df_spec = {
                    "build_fid": bfid, "scan_node": scan_ids[0],
                    "column": column, "wait_ms": wait_ms}

    def _await_dynamic_filter(self, stages: Dict[int, _Stage],
                              spec: dict) -> Optional[dict]:
        """Poll the build stage's TaskInfos until every task FINISHED
        and published its key domain, bounded by `wait_ms`. Any miss —
        deadline, failed/killed build worker, no domain published —
        degrades to None (unfiltered probe scan): a dynamic filter is
        an optimization, never a correctness dependency."""
        build = stages.get(spec["build_fid"])
        if build is None or build.df_publish_channel is None \
                or not build.task_uris:
            return None
        ch = str(build.df_publish_channel)
        deadline = time.time() + spec["wait_ms"] / 1000.0
        while True:
            domains = []
            done = True
            for uri in build.task_uris:
                try:
                    info = self.http.get_json(
                        uri, request_class="status_poll")
                except Exception:  # noqa: BLE001 — degrade, never block
                    return None
                state = (info.get("taskStatus") or {}).get("state")
                if state in ("FAILED", "ABORTED", "CANCELED"):
                    return None
                if state != "FINISHED":
                    done = False
                    continue
                d = ((info.get("stats") or {})
                     .get("dynamicFilterDomains") or {}).get(ch)
                if d is None:
                    return None   # finished without a domain (e.g.
                                  # string key): nothing to wait for
                domains.append(d)
            if done:
                break
            if time.time() > deadline:
                return None
            time.sleep(0.02)
        col = spec["column"]
        if sum(int(d.get("count", 0) or 0) for d in domains) == 0:
            return {"column": col, "empty": True}
        mins = [d["min"] for d in domains if d.get("min") is not None]
        maxs = [d["max"] for d in domains if d.get("max") is not None]
        if not mins:
            return None
        con = {"column": col, "min": min(mins), "max": max(maxs)}
        vals: Optional[set] = set()
        for d in domains:
            v = d.get("values")
            if v is None:
                vals = None
                break
            vals.update(v)
        if vals:
            con["values"] = sorted(vals)
        return con

    # ------------------------------------------------------------------
    def _start_stage(self, qid: str, fid: int, stages: Dict[int, _Stage],
                     by_id, placement: List[str]):
        stage = stages[fid]
        self._ensure_scan_splits(stage)
        # probe stage with a pending dynamic filter: wait (bounded) for
        # the build stage's domain BEFORE posting tasks, so the
        # constraint rides the very first split assignment
        if stage.df_spec is not None and stage.df_constraint is None:
            stage.df_constraint = self._await_dynamic_filter(
                stages, stage.df_spec)
        # cache-affinity placement: when result caching is on, route each
        # leaf task to the worker that (per the router's memory) holds
        # its fragment's cached result; rendezvous hashing places
        # never-seen fingerprints deterministically so the FIRST and
        # SECOND execution agree on a worker even with no history
        affinity_fp = None
        if stage.spec.scan_nodes and not stage.spec.remote_nodes and \
                str(self.session_properties.get(
                    "fragment_result_cache_enabled", "")
                    ).strip().lower() == "true":
            from presto_tpu.plan.fingerprint import plan_fingerprint
            try:
                affinity_fp = plan_fingerprint(by_id[fid].root)
            except Exception:   # noqa: BLE001 — affinity is advisory
                affinity_fp = None
        for t in range(stage.n_tasks):
            worker = placement[t % len(placement)]
            if stage.mesh_worker is not None:
                if stage.mesh_worker in placement:
                    # co-location: the fused mesh stage lands on the
                    # worker whose slice the planner chose
                    worker = stage.mesh_worker
                else:
                    # chosen mesh worker left between planning and
                    # placement — any survivor runs the same fragment
                    # (mesh-lowered if it has a slice, else generic)
                    from presto_tpu.server.mesh_tier import \
                        note_plan_fallback
                    note_plan_fallback("placement")
            if affinity_fp is not None:
                key = f"{affinity_fp}|t{t}/{stage.n_tasks}"
                picked = self.affinity.pick(key, placement)
                if picked is not None:
                    worker = picked
                self.affinity.record(key, worker)
            task_id, uri = self._post_stage_task(
                qid, fid, stages, by_id, worker, t, attempt=0)
            stage.task_ids.append(task_id)
            stage.task_uris.append(uri)

    def _ensure_scan_splits(self, stage: _Stage):
        """Bind connector splits (one list per scan node, split t to
        task t; reference: ConnectorSplitManager). Lazy so that EVERY
        post path computes them: a worker death during scheduling can
        leave a stage with no tasks posted, and recovery then creates
        its tasks without ever passing through _start_stage — a task
        posted without scan sources would fall back to scanning the
        whole table (SplitExecutor._fetch), duplicating rows once per
        task. Split assignment is a pure function of (fragment,
        n_tasks), so first-caller-wins is deterministic."""
        if stage.scan_splits or not stage.spec.scan_nodes:
            return
        stage.scan_splits = {
            node_id: (self.connector.connector_id(table),
                      self.connector.table_splits(table, stage.n_tasks))
            for node_id, table in stage.spec.scan_nodes.items()}

    def _producer_location(self, producer: _Stage, i: int,
                           uri: str) -> str:
        """Result location of producer task `i` as a consumer should
        see it NOW: normally the live task's URI; for a spool-absorbed
        task, a LIVE worker's URI with the COMMITTED attempt's task id
        — any worker sharing the spool base serves a committed spool
        over the same GET .../results/... protocol, so replacement
        consumers never dial the dead host."""
        if i not in producer.spool_done:
            return uri
        live = self.worker_uris
        host = (live[i % len(live)] if live
                else uri.split("/v1/task/")[0])
        return f"{host}/v1/task/{producer.spool_task_ids[i]}"

    def _post_stage_task(self, qid: str, fid: int, stages, by_id,
                         worker_uri: str, t: int, attempt: int):
        """POST task index `t` of fragment `fid` to one worker. The
        split assignment is a pure function of (fragment, t), so a
        recovery re-post on another worker re-reads exactly the same
        lifespans (reference: scheduler/group recoverable grouped
        execution; attempt is the Presto task-id attempt field)."""
        stage = stages[fid]
        spec = stage.spec
        self._ensure_scan_splits(stage)
        task_id = f"{qid}.{fid}.0.{t}.{attempt}"
        uri = f"{worker_uri}/v1/task/{task_id}"
        sources: List[S.TaskSource] = []
        seq = 0
        for node_id, (cid, all_splits) in stage.scan_splits.items():
            payload = all_splits[t]
            if stage.df_constraint is not None \
                    and stage.df_spec is not None \
                    and node_id == stage.df_spec["scan_node"]:
                payload = constrain_split_payload(
                    payload, stage.df_constraint)
            splits = [S.ScheduledSplit(
                sequenceId=seq, planNodeId=node_id,
                split=S.Split(connectorId=cid,
                              connectorSplit=payload))]
            seq += 1
            sources.append(S.TaskSource(planNodeId=node_id,
                                        splits=splits,
                                        noMoreSplits=True))
        for node_id, pfid in spec.remote_nodes.items():
            producer = stages[pfid]
            part = by_id[pfid].partitioning
            off = producer.buffer_offset.get(fid, 0)
            buffer_id = (str(off) if part == Partitioning.SINGLE
                         else str(off + t))
            splits = []
            for i, u in enumerate(producer.task_uris):
                splits.append(S.ScheduledSplit(
                    sequenceId=seq, planNodeId=node_id,
                    split=S.Split(connectorId="$remote",
                                  connectorSplit=remote_split_payload(
                                      self._producer_location(
                                          producer, i, u),
                                      buffer_id))))
                seq += 1
            sources.append(S.TaskSource(planNodeId=node_id,
                                        splits=splits,
                                        noMoreSplits=True))
        props = dict(self.session_properties)
        if stage.df_publish_channel is not None:
            # marks this task as a dynamic-filter build source; the
            # worker summarizes this output channel's key domain
            props["x_dynamic_filter_channel"] = str(
                stage.df_publish_channel)
        if stage.mesh_descriptor is not None:
            # ICI exchange routing side channel — stamped through the
            # mesh_tier chokepoint so recovery re-posts (any attempt,
            # any worker) carry the SAME descriptor
            from presto_tpu.server.mesh_tier import stamp_ici_descriptor
            stamp_ici_descriptor(props, stage.mesh_descriptor)
        tur = S.TaskUpdateRequest(
            session=S.SessionRepresentation(
                queryId=qid, user="cluster",
                systemProperties=props),
            extraCredentials={},
            fragment=spec.fragment.to_bytes(),
            sources=sources,
            outputIds=S.OutputBuffers(
                type="PARTITIONED", version=1, noMoreBufferIds=True,
                buffers={str(j): j for j in range(stage.n_buffers)}))
        body = tur.dumps().encode()
        tried = set()
        while True:
            try:
                # the TaskUpdateRequest round trip, the worker's
                # task_create with it
                with TRACER.span(None, "task_post", task=task_id,
                                 worker=worker_uri, bytes=len(body)):
                    self._post(uri, body)
                return task_id, uri
            except FatalResponseError as e:
                if not e.draining:
                    raise
                # graceful decommission mid-schedule: the worker
                # refused the NEW task with 410 + X-Presto-Draining
                # (the transport already recorded breaker SUCCESS on
                # the 4xx — a draining node takes no availability
                # penalty). Mark it drained through the chokepoint and
                # re-place this task on another live worker.
                err, mutation = e, {"drained_add": [worker_uri]}
                log.info("worker %s draining; re-placing task %s",
                         worker_uri, task_id)
            except TransportError as e:
                # the target died between the membership snapshot and
                # this POST (continuous churn): mark it dead through
                # the chokepoint and re-place instead of failing the
                # query. Safe even if the POST half-landed — task
                # updates are at-least-once and split assignment is
                # deterministic, so a duplicate produces identical
                # output under one task id.
                err, mutation = e, {"dead_add": [worker_uri]}
                log.info("worker %s unreachable; re-placing task %s",
                         worker_uri, task_id)
            tried.add(worker_uri)
            live = [w for w in self._membership(**mutation)
                    if w not in tried]
            if not live:
                raise ClusterQueryError(
                    f"no live workers to place task {task_id}: "
                    f"all candidates draining or dead") from err
            worker_uri = live[t % len(live)]
            uri = f"{worker_uri}/v1/task/{task_id}"

    # ------------------------------------------------------------------
    def _post(self, uri: str, body: bytes) -> dict:
        # TaskUpdateRequest POSTs are at-least-once by protocol (the
        # worker dedupes splits by sequenceId), so transport retries of
        # a dropped response are safe
        return self.http.post(uri, body,
                              request_class="task_post").json()

    def _await_all(self, stages: Dict[int, _Stage],
                   timeout_s: float = 1800, cancel_event=None,
                   query_id: Optional[str] = None):
        """Long-poll every task CONCURRENTLY (reference: one
        ContinuousTaskStatusFetcher per task) — a straggler in one stage
        no longer hides a failure in another, and N tasks cost one
        round-trip time per sweep instead of N. query_max_execution_time
        (when set) caps the wait below the scheduler default."""
        try:
            budget = float(self.session_properties.get(
                "query_max_execution_time", 0) or 0)
        except (TypeError, ValueError):
            budget = 0
        if budget > 0:
            timeout_s = min(timeout_s, budget)
        deadline = time.time() + timeout_s
        # spool-absorbed tasks are DONE by definition (their committed
        # output is the result) — never poll their dead location
        uris = [u for st in stages.values()
                for i, u in enumerate(st.task_uris)
                if i not in st.spool_done]
        results: Dict[str, Optional[dict]] = {}
        errs: Dict[str, BaseException] = {}
        wake = threading.Event()          # first failure OR all done
        remaining = [len(uris)]
        lock = threading.Lock()
        # the watchers record `task_notice` for this thread's trace
        here = TRACER.here()

        def _notice(uri: str, st: dict, polls: int) -> None:
            """`task_notice`: from the worker's terminal transition
            (`stateChangedAt`, the worker's span clock) to this answer
            of its status poll; none where the worker does not say."""
            at = st.get("stateChangedAt")
            if at is None:
                return
            end = now()
            TRACER.record(here.trace_id if here else None, "task_notice",
                          min(float(at), end), end,
                          parent_id=here.parent_span_id if here else "",
                          mark=True, task=uri.rsplit("/", 1)[-1],
                          state=st["state"], polls=polls)

        def watch(uri: str):
            state = "PLANNED"
            polls = 0
            try:
                while state in ("PLANNED", "RUNNING"):
                    if wake.is_set() and errs:
                        return            # another task already failed
                    if time.time() > deadline:
                        raise ClusterQueryError(f"timeout on {uri}")
                    st = self.http.get_json(
                        f"{uri}/status",
                        headers={"X-Presto-Current-State": state,
                                 "X-Presto-Max-Wait": "1s"},
                        request_class="status_poll")
                    polls += 1
                    state = st["state"]
                _notice(uri, st, polls)
                results[uri] = st
                if state != "FINISHED":
                    msgs = [f.get("message", "") for f in
                            st.get("failures", [])]
                    raise ClusterQueryError(
                        f"task {uri} {state}: " + "\n".join(msgs))
            except BaseException as e:    # noqa: BLE001 — re-raised below
                errs[uri] = e
                wake.set()                # fail fast
            finally:
                with lock:
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        wake.set()

        threads = [spawn("coordinator", f"task-watch-{i}", watch,
                         args=(u,), start=False)
                   for i, u in enumerate(uris)]
        for t in threads:
            t.start()
        # wake on the FIRST failure (fail-fast) or when every watcher
        # finished; stragglers are daemons and die with their long-poll
        # wait in slices so a client DELETE (statement cancellation)
        # interrupts the query instead of merely flagging it: tasks are
        # aborted by the caller's cleanup once we raise
        end = deadline + 60
        while not wake.is_set() and time.time() < end:
            if cancel_event is not None and cancel_event.is_set():
                raise ClusterQueryError("Query was canceled by the user")
            self._memory_kill_sweep(query_id)
            wake.wait(0.25)
        self._memory_kill_sweep(query_id)
        for uri, e in errs.items():
            raise e if isinstance(e, (ClusterQueryError, OSError)) \
                else ClusterQueryError(f"task {uri}: {e}")
        for uri in uris:
            if results.get(uri) is None:
                raise ClusterQueryError(f"no status from {uri}")

    def _memory_kill_sweep(self, query_id: Optional[str]) -> None:
        """Cluster low-memory killer (ClusterMemoryManager.java:106 +
        LowMemoryKiller): when aggregate reservations exceed the
        cluster budget, mark the single biggest query killed; when THIS
        query is the victim, surface the terminal
        EXCEEDED_MEMORY_LIMIT-class error (never retried — see
        ClusterMemoryKillError)."""
        cm = self.cluster_memory
        if cm is None or not self.memory_config.kill_enabled:
            return
        from presto_tpu.exec.memory import ExceededMemoryLimitError
        victim = cm.maybe_kill()
        if victim is not None:
            log.warning("low-memory killer chose query %s", victim)
        if query_id is None:
            return
        try:
            cm.check_killed(query_id)
        except ExceededMemoryLimitError as e:
            raise ClusterMemoryKillError(str(e)) from e

    def _collect_root(self, root: _Stage, out_types,
                      merge_keys=None) -> List[tuple]:
        with TRACER.span(None, "collect_root") as sp:
            if merge_keys:
                rows = self._merge_root(root, out_types, merge_keys)
            else:
                rows = self._drain_root(root, out_types, sp)
            sp.attributes["rows"] = len(rows)
        return rows

    def _drain_root(self, root: _Stage, out_types, span) -> List[tuple]:
        # concurrent final-result drain: all root tasks' buffers pull in
        # parallel through the bounded exchange buffer; arrival-order
        # interleaving is legal here because ordered results always
        # carry merge_keys (the _merge_root path), and single-task roots
        # keep exact order (per-stream FIFO)
        locations = [(self._producer_location(root, i, uri), "0")
                     for i, uri in enumerate(root.task_uris)]
        rows: List[tuple] = []
        with ExchangeClient(locations, types=list(out_types),
                            config=self.exchange_config,
                            client=self.http, spool=self.spool) as xc:
            for pages in xc:
                for p in pages:
                    rows.extend(p.to_pylist())
            span.attributes["bytes"] = xc.bytes_pulled
        return rows

    #: per-stream cap on decoded-but-unmerged row batches held at the
    #: coordinator during an ordered-merge collect
    MERGE_QUEUE_PAGES = 4

    def _merge_root(self, root: _Stage, out_types,
                    merge_keys) -> List[tuple]:
        """Ordered-merge exchange at the coordinator
        (operator/MergeOperator.java semantics at the root
        ExchangeClient). The per-task streams drain CONCURRENTLY
        (network overlap across workers) but coordinator residency is
        RE-BOUND: each stream's decoded batches flow through a bounded
        queue into ``heapq.merge`` instead of fully materializing every
        run before a Timsort pass — peak memory is
        ``k * (MERGE_QUEUE_PAGES + 2)`` batches plus the merged output,
        not the sum of all runs twice over."""
        def source(uri):
            def batches():
                for p in stream_pages(
                        uri, buffer_id="0", types=out_types,
                        client=self.http, spool=self.spool,
                        max_size_bytes=self.exchange_config
                        .max_response_bytes):
                    yield p.to_pylist()
            return batches

        class _Key:
            """SQL sort-order comparison over python row values (null
            ordering + per-key direction)."""
            __slots__ = ("row",)

            def __init__(self, row):
                self.row = row

            def __lt__(self, other):
                for k in merge_keys:
                    a = self.row[k.field]
                    b = other.row[k.field]
                    if a is None or b is None:
                        if (a is None) != (b is None):
                            return (a is None) == k.nulls_sort_first
                        continue
                    # NaN sorts after every non-null value regardless of
                    # direction (the shard sort is total-order NaN-last)
                    a_nan = isinstance(a, float) and a != a
                    b_nan = isinstance(b, float) and b != b
                    if a_nan or b_nan:
                        if a_nan != b_nan:
                            return b_nan
                        continue
                    if a == b:
                        continue
                    return (a < b) == k.ascending
                return False

        rows, high = bounded_merge(
            [source(self._producer_location(root, i, u))
             for i, u in enumerate(root.task_uris)], key=_Key,
            queue_pages=self.MERGE_QUEUE_PAGES)
        # observability hook for the bounded-in-flight test
        self.last_merge_inflight_high = high
        _M_MERGE_HIGH.set_max(high)
        return rows

    def _cleanup(self, stages: Dict[int, _Stage], qid: str = ""):
        for stage in stages.values():
            for i, uri in enumerate(stage.task_uris):
                if i in stage.spool_done:
                    continue       # nothing live behind a spooled task
                try:
                    self.http.delete(uri)
                except Exception:   # noqa: BLE001 — best-effort abort
                    pass
        # end-of-query spool retention: the query's whole spool tree
        # goes away with the query (success or failure)
        if self.spool is not None and qid:
            self.spool.gc_query(qid)
