"""Session properties + engine configuration.

Reference roles: SystemSessionProperties (presto-main-base/.../
SystemSessionProperties.java — 305 typed, per-query-overridable knobs in
one registry) and the native worker's SystemConfig
(presto_cpp/main/common/Configs.h:162). Scoped to the knobs this engine
actually consumes; each property declares a type and default, values
parse from strings exactly like session properties on the wire
(SessionRepresentation.systemProperties).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("true", "1", "yes", "on")


def _parse_bytes(s: str) -> int:
    s = s.strip().upper()
    for suffix, mult in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10),
                         ("B", 1)):
        if s.endswith(suffix):
            return int(float(s[:-len(suffix)]) * mult)
    return int(s)


@dataclasses.dataclass(frozen=True)
class Property:
    name: str
    description: str
    parse: Callable[[str], Any]
    default: Any


# The registry — one row per knob, like SystemSessionProperties' list.
PROPERTIES = [
    Property("query_max_memory_per_node",
             "Static plan-footprint limit per query; exceeding it raises "
             "MemoryLimitExceeded (or triggers lifespan batching)",
             _parse_bytes, None),
    Property("lifespan_batches",
             "Row-range lifespans to stream the driving scan in "
             "(0 = single shot)", int, 0),
    Property("streaming_scan_rows",
             "Bound the rows a driving leaf scan materializes at once: "
             "each lifespan streams through the partial plan in scan "
             "runs of at most this many rows (0 = whole-split "
             "materialization; the SF10 scale-ladder knob)", int, 0),
    Property("group_count_hint",
             "Default aggregation output-capacity hint when the planner "
             "has no estimate", int, 65536),
    Property("exchange_chunk_factor",
             "Per-peer exchange chunk = factor * capacity / n_devices",
             int, 2),
    Property("collect_stats",
             "Record per-node output row counts for EXPLAIN ANALYZE",
             _parse_bool, False),
    Property("cte_materialization_enabled",
             "Execute WITH subqueries referenced more than once into "
             "temp tables instead of inlining per reference (reference: "
             "PhysicalCteOptimizer / cte_materialization_strategy)",
             _parse_bool, False),
    Property("spill_enabled",
             "Offload accumulated lifespan partials out of device HBM "
             "(reference: spiller/ + revocable memory): host RAM by "
             "default, disk when spill_path is set",
             _parse_bool, True),
    Property("spill_path",
             "Directory for spill files (FileSingleStreamSpiller role; "
             "empty = host-RAM offload only)", str.strip, ""),
    Property("broadcast_join_threshold_rows",
             "Estimated build-side rows under which a join replicates "
             "its build instead of hash-exchanging both sides "
             "(reference: join_distribution_type AUTOMATIC + "
             "join_max_broadcast_table_size)", int, 50_000),
    Property("dynamic_filtering_enabled",
             "Prune driving-scan lifespans whose join-key range cannot "
             "match the build side (reference: "
             "enable_dynamic_filtering / DynamicFilterSourceOperator)",
             _parse_bool, True),
    Property("dynamic_filter_wait_ms",
             "Upper bound (milliseconds) a probe-side stage waits for a "
             "tiny build fragment's key domain before scheduling its "
             "scans unfiltered (cross-exchange dynamic filtering; "
             "reference: experimental.dynamic-filtering max blocking "
             "wait)", int, 400),
    Property("join_reordering_enabled",
             "Commute inner equi-joins so the smaller estimated side "
             "becomes the hash build (plan/iterative.ReorderJoins, "
             "history-first estimates; reference: "
             "join_reordering_strategy AUTOMATIC)", _parse_bool, True),
    Property("join_distribution_type",
             "AUTOMATIC (cost-based broadcast-vs-repartition) | "
             "PARTITIONED (always hash exchanges) | BROADCAST (force "
             "replicated builds where legal); reference: "
             "SystemSessionProperties.JOIN_DISTRIBUTION_TYPE",
             str.strip, "AUTOMATIC"),
    Property("query_max_execution_time",
             "Wall-clock budget per query in seconds (0 = unlimited); "
             "exceeded -> the query FAILS (reference: "
             "QUERY_MAX_EXECUTION_TIME + QueryTracker enforcement)",
             float, 0.0),
    Property("hash_partition_count",
             "Tasks per hash-partitioned intermediate stage in the "
             "cluster (0 = one per worker; reference: "
             "SystemSessionProperties.HASH_PARTITION_COUNT)", int, 0),
    Property("exchange_compression_codec",
             "Compress exchange pages: none | zlib | gzip | lz4 "
             "(LZ4 block format in the native C++ codec; reference: "
             "exchange_compression_codec, PagesSerdeFactory + "
             "CompressionCodec.java:16)", str.strip, "none"),
    Property("fragment_result_cache_enabled",
             "Worker-side fragment result caching for eligible leaf "
             "fragments, keyed on semantic plan fingerprint + table "
             "versions + splits (reference: fragment_result_caching_"
             "enabled, Presto@Meta VLDB'23 worker result cache)",
             _parse_bool, False),
    Property("retry_policy",
             "Mid-query fault handling: NONE (a worker death fails the "
             "query, whole-query retry only) | TASK (task outputs spool "
             "to disaggregated storage and only the lost tasks re-plan "
             "onto survivors as attempt N+1; reference: retry-policy "
             "TASK, Presto@Meta VLDB'23 §3 / Project Tardigrade)",
             lambda s: s.strip().upper(), "NONE"),
    Property("cluster_mesh_enabled",
             "Route eligible cluster task fragments (join/agg-bearing, "
             "mesh-lowerable) through the worker device-mesh execution "
             "tier (server/mesh_tier.py), and let the coordinator fuse "
             "co-locatable stages onto one mesh worker so the "
             "repartition exchange rides ICI collectives instead of "
             "HTTP page pulls; any lowering failure falls back to the "
             "generic executor + HTTP path byte-for-byte",
             _parse_bool, False),
]

_BY_NAME = {p.name: p for p in PROPERTIES}


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """Intra-cluster HTTP transport knobs (reference: the reference
    engine's HttpClientConfig / ExchangeClientConfig — request timeouts,
    backoff schedule, failure-detector thresholds — one config object
    instead of per-call-site literals). Per-request-class timeouts and
    retry counts live here; `protocol/transport.py` builds its policy
    table from this registry."""

    # per-request-class (timeout seconds, attempts incl. the first try)
    probe_timeout_s: float = 2.0           # /v1/info liveness probes
    probe_attempts: int = 1                # a probe IS the retry
    control_timeout_s: float = 10.0        # ack / abort / delete / info
    control_attempts: int = 2
    page_fetch_timeout_s: float = 30.0     # results GETs (long-poll)
    page_fetch_attempts: int = 5           # ExchangeClient.java:322 role
    status_poll_timeout_s: float = 30.0    # task status long-polls
    status_poll_attempts: int = 3
    task_post_timeout_s: float = 60.0      # TaskUpdateRequest POSTs
    task_post_attempts: int = 4            # at-least-once update protocol
    announce_timeout_s: float = 5.0        # discovery announcements
    announce_attempts: int = 1             # the announcer loop re-tries
    statement_timeout_s: float = 30.0      # client statement protocol
    statement_attempts: int = 3
    remote_function_timeout_s: float = 60.0
    remote_function_attempts: int = 3

    # exponential backoff + full jitter between retryable failures
    retry_base_backoff_s: float = 0.05
    retry_max_backoff_s: float = 2.0
    # total time a single logical request may spend retrying
    retry_budget_s: float = 15.0

    # per-worker circuit breaker (HeartbeatFailureDetector role):
    # consecutive failures to OPEN, then a cooldown before ONE
    # half-open probe may test whether the worker recovered
    breaker_failure_threshold: int = 3
    breaker_cooldown_s: float = 5.0

    # cap on a server-advised Retry-After sleep (overload responses,
    # 429 / 503 + Retry-After header); the retry budget still applies
    retry_after_max_s: float = 30.0


#: process defaults; tests construct their own with tighter windows
DEFAULT_TRANSPORT = TransportConfig()


@dataclasses.dataclass(frozen=True)
class NetConfig:
    """Serving-tier knobs (reference: the reference engine's
    HttpServerConfig — acceptor/selector threads, max request header
    size, idle connection timeout — plus HttpClientConfig's connection
    pool sizing). One per process; `net/aio_server.AioHttpServer` and
    the keep-alive pool in `protocol/transport.py` are built from
    this."""

    # -- server (event-loop front door) ------------------------------
    #: bounded executor threads for CPU/blocking handler dispatch —
    #: the only per-server thread growth (no thread-per-connection)
    executor_workers: int = 8
    #: slowloris guard: a connection that has not delivered complete
    #: request headers within this window is closed
    header_timeout_s: float = 10.0
    #: close a keep-alive connection idle (between requests) this long
    idle_timeout_s: float = 60.0
    #: cap on concurrently open server connections; beyond it new
    #: accepts are closed immediately (pool exhaustion is load-shed at
    #: the door, not queued into memory)
    max_connections: int = 4096
    #: event-loop lag heartbeat cadence: a timer fires at this interval
    #: and the observed overshoot lands in
    #: `net_event_loop_lag_seconds` — blocked-loop detection
    loop_lag_tick_s: float = 0.25
    #: spooled result ranges at least this large go out via
    #: `os.sendfile` instead of read+write (small ranges aren't worth
    #: the extra syscalls)
    sendfile_min_bytes: int = 4096

    # -- client (keep-alive connection pool) -------------------------
    #: idle pooled connections kept per destination host:port
    pool_per_host: int = 8
    #: evict a pooled connection idle longer than this (must stay
    #: under typical server idle_timeout_s so we rarely pick up a
    #: connection the server is about to close)
    pool_idle_ttl_s: float = 30.0


#: process defaults; tests construct their own with tighter windows
DEFAULT_NET = NetConfig()


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Fragment-result-cache knobs (reference: FragmentCacheStats +
    fragment-result-cache config in the native worker; Presto@Meta
    VLDB'23 §4.2). One per worker process — the task manager builds its
    `FragmentResultCache` from this."""

    #: master switch for the worker-side store (the session property
    #: `fragment_result_cache_enabled` additionally gates per query)
    enabled: bool = True
    #: byte budget for cached pages on one worker
    budget_bytes: int = 256 << 20
    #: refuse entries larger than this (one giant scan must not wipe
    #: the whole cache); 0 = budget_bytes
    max_entry_bytes: int = 32 << 20
    #: mirror cached bytes into the node MemoryPool so cache residency
    #: competes with execution reservations
    account_in_memory_pool: bool = False

    def entry_cap(self) -> int:
        return self.max_entry_bytes or self.budget_bytes


#: process defaults
DEFAULT_CACHE = CacheConfig()


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability knobs (reference: the Prometheus exporter config in
    the native worker + the coordinator's tracing/event-listener
    enablement). One per process; `obs/metrics.py` instruments and the
    cluster's trace sampling consult it."""

    #: master switch for metric collection (endpoints still respond,
    #: counters simply stay at their last value when off)
    metrics_enabled: bool = True
    #: master switch for span recording / trace propagation
    tracing_enabled: bool = True
    #: fraction of cluster queries that carry a trace (1.0 = all);
    #: unsampled queries send no X-Presto-Trace header, so workers open
    #: no spans for them
    trace_sample_rate: float = 1.0
    #: per-trace span cap forwarded to utils/tracing.Tracer — beyond it
    #: spans are counted as dropped instead of accumulating
    max_spans_per_trace: int = 2048
    #: wall-time histogram buckets (seconds)
    time_buckets_s: tuple = (0.001, 0.005, 0.025, 0.1, 0.5, 2.5, 10.0,
                             30.0, 120.0)
    #: row-count histogram buckets
    rows_buckets: tuple = (1.0, 100.0, 10_000.0, 100_000.0,
                           1_000_000.0, 10_000_000.0, 100_000_000.0)
    #: wide-event query log sink (obs/wide_events.py): JSONL path the
    #: coordinator appends one QueryCompletedEvent to per cluster query;
    #: None keeps the in-memory ledger only. PRESTO_TPU_EVENT_LOG
    #: overrides at sink-install time.
    event_log_path: Optional[str] = None
    #: rotate the event log when it exceeds this many bytes
    event_log_max_bytes: int = 16 << 20
    #: rotated generations kept (event_log.1 .. event_log.N)
    event_log_max_files: int = 3
    #: always-on sampling profiler (obs/profiler.py) master switch
    profiler_enabled: bool = True
    #: profiler sampling frequency (Hz); the sampler self-throttles
    #: whenever its own cost exceeds `profiler_max_overhead`
    profiler_hz: float = 97.0
    #: retained stack buckets per (role, purpose, query) key
    profiler_top_k: int = 64
    #: frames kept per sampled stack (deepest-callee end)
    profiler_max_depth: int = 24
    #: self-time budget as a fraction of wall time — above it the
    #: sampler doubles its sleep until it is back under budget
    profiler_max_overhead: float = 0.01

    # -- telemetry history (obs/tsdb.py) -----------------------------
    #: master switch for the in-process time-series store + scraper
    tsdb_enabled: bool = True
    #: history retention window (seconds): points older than this are
    #: dropped from every series (ring-buffer bound, per series)
    tsdb_retention_s: float = 900.0
    #: minimum spacing between stored points per series (the write
    #: chokepoint drops anything closer than this to the series'
    #: newest point)
    tsdb_resolution_s: float = 0.05
    #: minimum spacing between heartbeat-path scrape SWEEPS — pump
    #: loops and probers may call check_workers() at tens of Hz, but a
    #: full sweep (registry render + one HTTP fetch per live worker +
    #: parse) runs at most this often; query-bracket sweeps bypass
    #: this throttle (force=True) but fetch no workers
    tsdb_sweep_interval_s: float = 2.0
    #: series cap: beyond it new series are dropped (counted in
    #: `obs_scrape_points_dropped_total`) instead of growing unbounded
    tsdb_max_series: int = 16384
    #: hard cap on retained points per series (rings are bounded by
    #: BOTH retention_s and this count)
    tsdb_max_points: int = 2048
    #: scraper self-time budget as a fraction of wall time — the same
    #: methodology as profiler_max_overhead: when cumulative scrape
    #: self-time exceeds this fraction, scrapes are skipped until the
    #: ratio is back under budget (<1% overhead by construction)
    tsdb_max_overhead: float = 0.01

    # -- alerting (obs/alerts.py) ------------------------------------
    #: master switch for alert-rule evaluation (rules stay registered,
    #: evaluation is skipped when off)
    alerts_enabled: bool = True
    #: default evaluation window (seconds) for rules that do not set
    #: their own — thresholds look at the latest sample in the window,
    #: burn-rate rules at the counter increase across it
    alert_window_s: float = 60.0
    #: default pending->firing dwell (seconds) for rules that do not
    #: set their own `for_s`
    alert_for_s: float = 10.0
    #: alert-transition history ring capacity (system.runtime.alerts
    #: and the wide-event sink both read from it)
    alert_history_cap: int = 256

    def sampled(self, rng_value: float) -> bool:
        """Decide sampling from a caller-supplied uniform [0,1) draw
        (kept injectable for deterministic tests)."""
        return self.tracing_enabled \
            and rng_value < self.trace_sample_rate


#: process defaults
DEFAULT_OBS = ObsConfig()


@dataclasses.dataclass(frozen=True)
class SpoolConfig:
    """Spooled-exchange knobs (reference: the exchange-manager /
    exchange.base-directories config behind Presto's TASK retry policy —
    Presto@Meta VLDB'23 §3, Trino Project Tardigrade). One per process;
    `spool/store.SpoolStore` is built from this. The shared `base_dir`
    plays the role of disaggregated storage: every node of a cluster
    must see the same directory."""

    #: master switch for the worker-side spool store (the session
    #: property `retry_policy=TASK` additionally gates per query)
    enabled: bool = False
    #: shared spool root; None = the store creates its own temp root
    base_dir: Optional[str] = None
    #: SerializedPage frame compression for spooled pages
    codec: str = "lz4"
    #: sweep committed/partial spools left by dead processes when a
    #: store opens over an existing base_dir
    sweep_on_start: bool = True
    #: only sweep orphans older than this many seconds (0 = any age)
    orphan_ttl_s: float = 0.0


#: process defaults — off: spooling costs a disk write per output page
DEFAULT_SPOOL = SpoolConfig()


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    """Concurrent-exchange knobs (reference: ExchangeClientConfig behind
    operator/ExchangeClient.java — maxBufferedBytes, maxResponseSize,
    concurrentRequestMultiplier). One per process; every
    `protocol/exchange.ExchangeClient` is built from this."""

    #: total decoded-chunk bytes (accounted by wire size) the client may
    #: hold in its in-flight buffer before fetchers park — the true
    #: backpressure bound (ExchangeClient.java maxBufferedBytes). An
    #: empty buffer always admits one chunk even if it alone exceeds
    #: the cap, so the effective bound is
    #: max(max_buffered_bytes, one chunk) and progress never deadlocks.
    max_buffered_bytes: int = 32 << 20
    #: per-GET response cap sent as X-Presto-Max-Size (ExchangeClient's
    #: maxResponseSize): one pull round never materializes more than
    #: this per stream
    max_response_bytes: int = 4 << 20
    #: simultaneous in-flight GETs across all of a client's streams
    #: (concurrentRequestMultiplier role); 0 = one per stream,
    #: unbounded across streams
    max_concurrent_fetchers: int = 16
    #: X-Presto-Max-Wait long-poll window per GET
    max_wait: str = "1s"


#: process defaults
DEFAULT_EXCHANGE = ExchangeConfig()


@dataclasses.dataclass(frozen=True)
class MeshTierConfig:
    """Cluster mesh execution tier knobs (server/mesh_tier.py): the
    worker-side device-mesh task runner plus the coordinator's
    co-location policy. Mirrors the reference's native-worker swap
    (PAPER.md L6a TaskExecutor / L7 exchange): the execution tier
    changes, the coordinator protocol does not."""

    #: worker side: advertise a mesh slice and accept mesh-lowered
    #: task fragments (per query still gated by the session property
    #: `cluster_mesh_enabled`)
    enabled: bool = True
    #: devices in this worker's mesh slice; 0 = every visible device
    ndev: int = 0
    #: ICI domain id — co-location requires producer and consumer to
    #: share one group (single-host default: every worker sees the
    #: same device set, so one group)
    mesh_group: str = "local"
    #: coordinator side: fuse co-locatable producer/consumer stages
    #: onto one mesh worker so the exchange rides ICI collectives
    colocate: bool = True
    #: refuse to fuse plans wider than this many HTTP-path fragments
    #: (a very wide plan concentrated on one worker loses more to lost
    #: scan parallelism than it gains from ICI exchange)
    max_colocate_fragments: int = 8


#: process defaults
DEFAULT_MESH_TIER = MeshTierConfig()


@dataclasses.dataclass(frozen=True)
class AdmissionConfig:
    """Statement front-door knobs (reference: dispatcher/
    DispatchManager + query-manager config — max-queued-queries,
    dispatcher concurrency — plus the resource-group manager's queue
    limits). One per coordinator; `admission/DispatchManager` and its
    `LoadShedder` are built from this."""

    #: bounded execution pool: how many statements run concurrently
    #: (replaces the old unbounded thread-per-query path)
    max_dispatch_threads: int = 8
    #: pool-thread housekeeping interval — queue-timeout eviction and
    #: memory-quota re-checks happen at least this often while idle
    dispatch_tick_s: float = 0.25
    #: default per-group queue timeout applied when a group does not
    #: set its own (None = wait forever, bounded by the client)
    default_queue_timeout_s: Optional[float] = None

    # -- load shedding thresholds ------------------------------------
    #: refuse new statements when this many are queued across all
    #: resource groups
    shed_max_queued: int = 256
    #: refuse when memory-pool reserved/budget reaches this fraction
    shed_heap_fraction: float = 0.95
    #: refuse when the recent p99 admission queue wait reaches this
    shed_queue_wait_p99_s: float = 20.0
    #: Retry-After interval advertised on shed responses
    retry_after_s: float = 1.0
    #: recent queue-wait samples kept for the p99 shedding signal and
    #: the /v1/status percentiles
    wait_window: int = 1024


#: process defaults
DEFAULT_ADMISSION = AdmissionConfig()


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Elastic-cluster knobs (reference: the graceful-shutdown handler
    in the native worker — PrestoServer's shutdown sequence drains
    tasks before exiting — plus Presto@Meta VLDB'23 §3's fluid worker
    membership). One per process; the worker's drain path and the
    coordinator's query journal are built from this."""

    #: upper bound a draining worker waits for its running tasks to
    #: finish before shutting down anyway (tasks past the deadline are
    #: left to TASK-retry recovery on the coordinator)
    drain_timeout_s: float = 30.0
    #: poll interval while waiting for running tasks to drain
    drain_poll_s: float = 0.05
    #: write-ahead query journal location; None = journaling off (the
    #: statement server keeps no crash-recoverable query log)
    journal_path: Optional[str] = None
    #: compact the journal (rewrite live records only) once the dead-
    #: record count crosses this threshold
    journal_compact_threshold: int = 256
    #: how long a coordinator restart keeps absorbing journaled RUNNING
    #: queries before declaring them failed (0 = re-run immediately)
    recover_grace_s: float = 0.0
    #: crash-recovery re-queue cap: a journaled query that has already
    #: been re-queued this many times by coordinator restarts is
    #: abandoned with a terminal FAILED record instead of re-running —
    #: under repeated coordinator crashes an unbounded recovery storm
    #: would otherwise clog admission with orphaned re-executions
    recover_max_requeues: int = 3


#: process defaults — journaling off: tests opt in with a tmp path
DEFAULT_ELASTIC = ElasticConfig()


@dataclasses.dataclass(frozen=True)
class MemoryConfig:
    """Memory-arbitration knobs (reference: NodeMemoryConfig +
    MemoryManagerConfig — query.max-memory-per-node and
    query.max-memory — plus the MemoryRevokingScheduler's
    revoking-threshold). One per process; each worker's
    `TaskManager` builds its node `MemoryPool` from this and the
    coordinator derives the cluster budget for the low-memory
    killer."""

    #: per-node pool budget (query.max-memory-per-node role): the sum
    #: of static plan footprints admitted on one worker; 0 disables
    #: arbitration (tasks run unpooled, the pre-PR-14 behavior)
    pool_bytes: int = 0
    #: fraction of the pool at which revocation hooks fire BEFORE a
    #: reservation can fail (memory-revoking-threshold role)
    revoke_threshold: float = 0.8
    #: cluster-wide query-memory budget for the low-memory killer
    #: (query.max-memory role); 0 derives it from the sum of worker
    #: pool budgets
    cluster_bytes: int = 0
    #: master switch for the coordinator's low-memory killer sweep —
    #: with it off an over-budget cluster only refuses new admissions
    kill_enabled: bool = True

    def cluster_budget(self, n_workers: int) -> int:
        if self.cluster_bytes:
            return self.cluster_bytes
        return self.pool_bytes * max(n_workers, 1)


#: process defaults — arbitration off: tests and benches opt in
DEFAULT_MEMORY = MemoryConfig()


@dataclasses.dataclass(frozen=True)
class MVConfig:
    """Materialized-view maintenance knobs (presto_tpu/mv/; reference:
    the incrementally maintained MV half of Presto@Meta's VLDB'23
    data-freshness story). One per MV manager."""

    #: byte budget of the pinned accumulator-state cache; MV state is
    #: pinned (never LRU-evicted) inside a FragmentResultCache, so this
    #: bounds total pinned bytes across all views
    state_budget_bytes: int = 64 << 20
    #: background refresher: a view whose base tables moved and whose
    #: last refresh is older than this gets re-refreshed by the
    #: mv-refresh admission tenant
    staleness_target_s: float = 5.0
    #: background refresher poll cadence
    refresh_tick_s: float = 0.5
    #: bounded full recompute: refuse a full-recompute refresh when the
    #: base tables hold more rows than this (the incremental path has
    #: no such bound — its cost scales with the delta, not the table)
    max_full_recompute_rows: int = 200_000_000
    #: MV definition journal location; None derives it from the
    #: elastic query-journal path (+ ".mv") when one is configured
    journal_path: Optional[str] = None
    #: compact the MV journal once dead records cross this threshold
    journal_compact_threshold: int = 64


DEFAULT_MV = MVConfig()


class Session:
    """One query session: defaults overridden by string-typed properties
    (the wire form). Unknown properties are rejected loudly, like the
    coordinator does."""

    def __init__(self, properties: Optional[Dict[str, str]] = None,
                 user: str = "user", catalog: str = "tpch",
                 schema: str = "default"):
        self.user = user
        self.catalog = catalog
        self.schema = schema
        self.values: Dict[str, Any] = {
            p.name: p.default for p in PROPERTIES}
        for name, raw in (properties or {}).items():
            prop = _BY_NAME.get(name)
            if prop is None:
                raise KeyError(f"unknown session property {name!r}")
            self.values[name] = prop.parse(raw)

    def __getitem__(self, name: str):
        return self.values[name]

    def get(self, name: str, default=None):
        return self.values.get(name, default)

    @staticmethod
    def describe() -> str:
        """SHOW SESSION analog."""
        out = []
        for p in PROPERTIES:
            out.append(f"{p.name} (default {p.default!r}): "
                       f"{p.description}")
        return "\n".join(out)
