"""Observability acceptance: Prometheus exposition correctness,
/v1/metrics + /v1/status on both node roles, tracer bounds, and
cross-node trace propagation under injected transport faults.

Reference roles: the native worker's PrometheusStatsReporter exposition
and the coordinator's JMX counters (obs/metrics.py docstring), plus the
OpenTelemetry-style task-level tracing the reference threads through
TaskUpdateRequest headers — here `X-Presto-Trace`."""

import json
import re
import urllib.request

import pytest

from presto_tpu.config import TransportConfig
from presto_tpu.connectors import TpchConnector
from presto_tpu.obs.metrics import MetricsRegistry, render_prometheus
from presto_tpu.server.cluster import TpuCluster
from presto_tpu.server.statement import StatementServer
from presto_tpu.testing import FaultInjector, FaultSpec
from presto_tpu.utils.tracing import (
    EventListenerManager, QueryEvent, TRACER, Tracer, parse_trace_header,
)

SF = 0.01

#: exposition sample line: name{labels} value  (comments aside)
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})? '
    r"(\+Inf|-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?)$")


def _assert_valid_exposition(text: str):
    """Every non-comment line must be a well-formed sample."""
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE.match(line), f"malformed sample line: {line!r}"


# ------------------------------------------------------------ registry unit

def test_counter_renders_help_type_and_value():
    r = MetricsRegistry()
    c = r.counter("t_requests_total", "Requests served")
    c.inc()
    c.inc(2)
    text = r.render()
    assert "# HELP t_requests_total Requests served" in text
    assert "# TYPE t_requests_total counter" in text
    assert "\nt_requests_total 3\n" in text
    _assert_valid_exposition(text)


def test_counter_rejects_negative_and_unlabeled_renders_zero():
    r = MetricsRegistry()
    c = r.counter("t_zero_total")
    with pytest.raises(ValueError):
        c.inc(-1)
    assert "t_zero_total 0" in r.render()


def test_label_value_escaping():
    r = MetricsRegistry()
    g = r.gauge("t_labeled", labelnames=("path",))
    g.set(1, path='a"b\\c\nd')
    text = r.render()
    assert 't_labeled{path="a\\"b\\\\c\\nd"} 1' in text
    _assert_valid_exposition(text)


def test_gauge_set_max_keeps_high_water():
    r = MetricsRegistry()
    g = r.gauge("t_high_water")
    g.set_max(5)
    g.set_max(3)
    assert g.value() == 5
    g.set_max(9)
    assert g.value() == 9


def test_histogram_buckets_cumulative():
    r = MetricsRegistry()
    h = r.histogram("t_wall_seconds", buckets=(0.25, 1.0, 10.0))
    for v in (0.125, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)        # binary-exact values: sum renders exactly
    text = r.render()
    assert 't_wall_seconds_bucket{le="0.25"} 1' in text
    assert 't_wall_seconds_bucket{le="1"} 3' in text
    assert 't_wall_seconds_bucket{le="10"} 4' in text
    assert 't_wall_seconds_bucket{le="+Inf"} 5' in text
    assert "t_wall_seconds_count 5" in text
    assert "t_wall_seconds_sum 56.125" in text
    _assert_valid_exposition(text)


def test_registration_idempotent_but_conflicts_raise():
    r = MetricsRegistry()
    a = r.counter("t_same_total", "first")
    b = r.counter("t_same_total", "second wording ignored")
    assert a is b
    with pytest.raises(ValueError):
        r.gauge("t_same_total")                   # kind conflict
    with pytest.raises(ValueError):
        r.counter("t_same_total", labelnames=("x",))   # label conflict
    with pytest.raises(ValueError):
        r.counter("0bad-name")
    with pytest.raises(ValueError):
        r.counter("t_ok_total", labelnames=("bad-label",))


def test_global_render_is_valid_exposition():
    # whatever the rest of the suite has poured into the process-global
    # registry so far, the combined page must still parse
    _assert_valid_exposition(render_prometheus())


# ------------------------------------------------------------- tracer unit

def test_tracer_span_cap_counts_drops():
    t = Tracer(max_traces=8, max_spans_per_trace=3)
    for i in range(5):
        t.record("t1", f"s{i}", 0.0, end=0.1)
    assert len(t.get("t1")) == 3
    assert t.dropped_spans("t1") == 2
    assert "2 span(s) dropped" in t.render("t1")


def test_tracer_evicts_oldest_trace():
    t = Tracer(max_traces=2, max_spans_per_trace=10)
    for qid in ("q1", "q2", "q3"):
        t.record(qid, "s", 0.0, end=0.1)
    assert t.get("q1") == []
    assert len(t.get("q3")) == 1


def test_merge_remote_dedupes_by_span_id():
    t = Tracer()
    s = t.record("qx", "task_run", 0.0, end=0.5, worker="w0")
    doc = t.to_json("qx")
    assert t.merge_remote("qx", doc) == 0       # same span id: no dupe
    doc["spans"][0]["spanId"] = "f" * 16
    assert t.merge_remote("qx", doc) == 1
    assert {x.span_id for x in t.get("qx")} == {s.span_id, "f" * 16}


def test_parse_trace_header():
    ctx = parse_trace_header("q_123;abcdef0123456789")
    assert ctx.trace_id == "q_123"
    assert ctx.parent_span_id == "abcdef0123456789"
    assert parse_trace_header(None) is None
    assert parse_trace_header("") is None
    assert parse_trace_header(" ;deadbeef") is None   # empty trace id
    # header without a parent segment still yields a usable context
    bare = parse_trace_header("q_9")
    assert bare.trace_id == "q_9" and bare.parent_span_id == ""


def test_event_listener_errors_counted_and_logged_once():
    from presto_tpu.obs.metrics import REGISTRY
    mgr = EventListenerManager()
    seen = []

    def bad(evt):
        raise RuntimeError("boom")

    mgr.register(bad)
    mgr.register(seen.append)
    c = REGISTRY.counter("presto_tpu_event_listener_errors_total")
    before = c.value()
    for i in range(3):
        mgr.emit(QueryEvent(kind="completed", query_id=f"q{i}", sql=""))
    assert c.value() == before + 3      # every swallow counted
    assert len(mgr._logged_failures) == 1   # ...but logged once
    assert len(seen) == 3               # healthy listener unaffected


# ------------------------------------------------------- HTTP endpoints

#: tight retry windows so the chaos leg resolves in test time
FAST_TRANSPORT = TransportConfig(
    retry_base_backoff_s=0.01, retry_max_backoff_s=0.2,
    retry_budget_s=5.0, breaker_failure_threshold=3,
    breaker_cooldown_s=0.3)


@pytest.fixture(scope="module")
def cluster():
    c = TpuCluster(TpchConnector(SF), n_workers=2,
                   transport_config=FAST_TRANSPORT)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def statement_server(cluster):
    srv = StatementServer(cluster).start()
    yield srv
    srv.stop()


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.read().decode(), dict(resp.headers)


def test_worker_metrics_endpoint(cluster):
    port = cluster.workers[0].port
    text, headers = _get(f"http://127.0.0.1:{port}/v1/metrics")
    assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
    _assert_valid_exposition(text)
    for needle in ("# TYPE presto_tpu_tasks gauge",
                   "presto_tpu_uptime_seconds",
                   "# TYPE presto_tpu_transport_breaker_state gauge",
                   "# TYPE presto_tpu_result_cache_hits_total counter",
                   "# TYPE presto_tpu_output_buffer_pages_added_total "
                   "counter"):
        assert needle in text, f"missing {needle!r}"


def test_worker_status_shape(cluster):
    port = cluster.workers[0].port
    text, _ = _get(f"http://127.0.0.1:{port}/v1/status")
    st = json.loads(text)
    assert st["role"] == "worker"
    assert st["nodeId"].startswith("tpu-worker-")
    for key in ("uptimeSeconds", "taskCount", "tasksCreated",
                "heapUsed", "heapAvailable"):
        assert key in st, f"missing status key {key}"
    assert st["uptimeSeconds"] >= 0


def test_coordinator_metrics_and_status(cluster, statement_server):
    want = cluster.execute_sql("select count(*) from nation")
    base = statement_server.base
    text, headers = _get(f"{base}/v1/metrics")
    assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
    _assert_valid_exposition(text)
    assert "presto_tpu_coordinator_uptime_seconds" in text
    # task traffic from the query above is visible in the registry
    assert re.search(
        r"presto_tpu_tasks_created_total [1-9]", text)

    st = json.loads(_get(f"{base}/v1/status")[0])
    assert st["role"] == "coordinator"
    assert st["nodeId"] == "tpu-coordinator"
    for key in ("uptimeSeconds", "queryCount", "heapUsed",
                "heapAvailable"):
        assert key in st, f"missing status key {key}"
    assert want == [(25,)]


# ------------------------------------- cross-node tracing, with chaos

class _TwoFaultsAHost(FaultInjector):
    """A 500 on each host's 2nd and 5th request and on no other. A
    drawn rate makes the number of faults grow with the number of
    status polls, i.e. with the machine's load, until three in a row
    open a healthy worker's breaker (threshold 3 here) and the query
    runs on one worker; two a host can never open it, and each is
    retried by its request's own policy."""

    def _roll(self, kind, host, ordinal):
        return 0.0 if kind == "http500" and ordinal in (1, 4) else 1.0


def test_trace_propagation_two_workers_under_retry(cluster):
    """A 2-worker query with an injected-retry transport yields ONE
    stitched trace: the coordinator's root `query` span plus task spans
    from BOTH workers parented under it, and the injected faults show
    up as retry + breaker metrics on the /v1/metrics page."""
    hosts = {u.split("://", 1)[1] for u in cluster.all_worker_uris}
    inj = _TwoFaultsAHost(spec=FaultSpec(http_500_rate=0.5),
                          only_hosts=hosts)
    cluster.http.fault_injector = inj
    try:
        rows = cluster.execute_sql("select count(*) from lineitem")
    finally:
        cluster.http.fault_injector = None
    assert rows[0][0] > 50_000     # SF 0.01 lineitem row count

    qid = cluster.last_trace_id
    spans = TRACER.get(qid)
    root = next(s for s in spans if s.name == "query")
    assert root.parent_id == ""
    task_spans = [s for s in spans if s.name == "task_run"]
    assert task_spans, "no worker task spans in the stitched trace"
    assert all(s.parent_id == root.span_id for s in task_spans), \
        "worker spans not parented under the coordinator root span"
    workers = {s.attributes.get("worker") for s in task_spans}
    assert len(workers) >= 2, f"expected both workers, got {workers}"

    # the trace surfaces in EXPLAIN ANALYZE and render_trace
    timeline = cluster.render_trace(qid)
    assert "query" in timeline and "tpu-worker-" in timeline

    # injected faults really fired, and rode into the registry
    assert inj.injected.get("http500", 0) > 0
    text = render_prometheus()
    assert re.search(
        r'presto_tpu_transport_retries_total\{host="[^"]+"\} [1-9]',
        text), "transport retries not visible in exposition"


#: the spans device idle time may be attributed to (PERF.md section 3)
LEAF_SPANS = {"admission_wait", "telemetry", "plan", "schedule",
              "collect_root", "task_create", "task_plan", "exchange_pull",
              "deserialize", "upload", "dispatch", "download", "serialize"}
CONTAINER_SPANS = {"statement", "query", "await_tasks", "task_run",
                   "exchange_wait", "device_wait"}


def test_served_statement_leaves_every_layers_span(cluster,
                                                   statement_server):
    """One statement through POST /v1/statement (two tables joined, a
    group by: scans, a page exchange, partial and final aggregation):
    every leaf span occurs under the statement's one trace id, nested
    under `query` on the workers and under `statement` on the
    coordinator, the byte counts ride on the spans, the `op:` spans lie
    where their islands ran, and no in-process worker is asked for its
    spans over HTTP."""
    from presto_tpu.server.statement import run_statement
    asked = []
    real_get = cluster.http.get_json

    def get_json(url, *a, **kw):
        asked.append(url)
        return real_get(url, *a, **kw)

    cluster.http.get_json = get_json
    try:
        _columns, rows = run_statement(
            statement_server.base,
            "select o_orderpriority, count(*), sum(l_extendedprice) "
            "from orders join lineitem on l_orderkey = o_orderkey "
            "group by o_orderpriority")
    finally:
        del cluster.http.get_json
    assert len(rows) == 5
    assert not [u for u in asked if "/v1/trace/" in u]

    spans = TRACER.get(cluster.last_trace_id)
    by_id = {s.span_id: s for s in spans}
    names = {s.name for s in spans}
    assert LEAF_SPANS <= names, LEAF_SPANS - names
    assert CONTAINER_SPANS <= names, CONTAINER_SPANS - names
    assert all(s.end is not None and s.end >= s.start for s in spans)
    assert not [s for s in spans if s.name == "task_create"
                and s.end == s.start]

    def chain(s):
        out = [s.name]
        while s.parent_id:
            s = by_id[s.parent_id]      # every parent is in the trace
            out.append(s.name)
        return out

    coordinator = {"statement", "admission_wait", "telemetry", "plan",
                   "query", "schedule", "await_tasks", "collect_root"}
    # the statement protocol's spans lie before and after `statement`
    protocol = {"statement_post", "statement_results"}
    for s in spans:
        c = chain(s)
        if s.name in protocol:
            assert c == [s.name], c
            continue
        assert c[-1] == "statement", c
        if s.name not in coordinator:
            assert "query" in c, c      # a worker's span, under the root
    root = next(s for s in spans if s.name == "query")
    assert all(s.parent_id == root.span_id for s in spans
               if s.name in ("task_run", "task_create"))
    for name in ("upload", "exchange_wait", "exchange_pull", "serialize",
                 "download", "deserialize"):
        got = [s.attributes.get("bytes", 0) for s in spans
               if s.name == name]
        assert got and sum(got) > 0, name
    assert {s.attributes.get("table") for s in spans
            if s.name == "upload"} >= {"orders", "lineitem"}
    programs = {s.attributes["program"] for s in spans
                if s.name == "dispatch"}
    assert programs and all(p.startswith("jit_presto_")
                            for p in programs)
    wait = next(s for s in spans if s.name == "admission_wait")
    assert wait.attributes["waited_ms"] >= 0 and chain(wait)[1] == \
        "statement"
    # an island ran after its task's inputs were pulled: an `op:` span
    # placed from the task's start would begin before the pull ended
    runs = {s.attributes["task"]: s for s in spans
            if s.name == "task_run"}
    ops = [s for s in spans if s.name.startswith("op:")]
    assert ops
    for op in ops:
        run = runs[op.attributes["task"]]
        assert run.start <= op.start and op.end <= run.end
        waits = [w for w in spans if w.name == "exchange_wait"
                 and w.parent_id == run.span_id]
        assert all(w.end <= op.start for w in waits)
    waits = {w.span_id: w for w in spans if w.name == "exchange_wait"}
    assert {w.parent_id for w in waits.values()} & \
        {runs[op.attributes["task"]].span_id for op in ops}
    # a fetcher's GET that landed data is a pull, inside the wait of the
    # consumer it fetched for (or the root's collect); both count bytes
    pulls = [p for p in spans if p.name == "exchange_pull"]
    for p in pulls:
        assert p.attributes["bytes"] > 0 and p.end > p.start
        # (under `task_run` itself where a task streams its input)
        assert chain(p)[1] in ("exchange_wait", "collect_root",
                               "task_run"), chain(p)
        w = waits.get(p.parent_id)
        assert w is None or w.start <= p.start and p.end <= w.end
    assert sum(w.attributes["bytes"] for w in waits.values()) == \
        sum(p.attributes["bytes"] for p in pulls if p.parent_id in waits)


def test_worker_trace_endpoint_serves_span_dump(cluster):
    qid = cluster.last_trace_id
    port = cluster.workers[0].port
    doc = json.loads(_get(f"http://127.0.0.1:{port}/v1/trace/{qid}")[0])
    assert doc["traceId"] == qid
    assert isinstance(doc["spans"], list) and doc["spans"]
    names = {s["name"] for s in doc["spans"]}
    assert "task_run" in names


def test_explain_analyze_carries_trace(cluster):
    out = cluster.explain_analyze_sql(
        "select count(*) from nation")
    assert "Trace " in out
    assert "tpu-worker-" in out
