"""Session-property config system + EXPLAIN ANALYZE observability tests.

VERDICT.md missing #8/#9: a typed session-property registry
(SystemSessionProperties analog) consumed by the executor, and the
OperatorStats/EXPLAIN ANALYZE reinterpretation (per-node cardinalities +
static footprints + wall time; fused nodes marked)."""

import json
import urllib.request

import pytest

from presto_tpu.config import PROPERTIES, Session
from presto_tpu.connectors import TpchConnector
from presto_tpu.data.column import Page
from presto_tpu.exec import LocalEngine
from presto_tpu.exec.executor import MemoryLimitExceeded


def test_session_property_parsing():
    s = Session({"query_max_memory_per_node": "2GB",
                 "lifespan_batches": "4",
                 "spill_enabled": "false"})
    assert s["query_max_memory_per_node"] == 2 << 30
    assert s["lifespan_batches"] == 4
    assert s["spill_enabled"] is False
    with pytest.raises(KeyError):
        Session({"not_a_property": "1"})
    assert len(Session.describe().splitlines()) == len(PROPERTIES)


def test_memory_limit_session_property():
    eng = LocalEngine(TpchConnector(0.01), session=Session(
        {"query_max_memory_per_node": "100KB"}))
    with pytest.raises(MemoryLimitExceeded):
        eng.execute_sql("select count(*) from lineitem")


def test_merge_join_agrees_with_hash_join_on_a_unique_build():
    """The executor picks `merge_join` itself wherever the build keys
    are unique (no session switch); `hash_join`, its fallback after
    duplicate build keys, is the reference: lineitem against orders,
    the same rows from both."""
    from presto_tpu.ops.join import hash_join, merge_join
    conn = TpchConnector(0.01)

    def two(table, *names):
        page = conn.table(table).page()
        return Page.from_columns(
            [page.column(page.names.index(n)) for n in names],
            page.num_rows, list(names))

    probe = two("lineitem", "l_orderkey", "l_linenumber")
    build = two("orders", "o_orderkey", "o_custkey")
    merged, dup, _match = merge_join(probe, build, [0], [0], "inner")
    hashed, pairs = hash_join(probe, build, [0], [0], probe.capacity,
                              "inner")
    assert int(dup) == 0
    assert int(pairs) == int(merged.num_rows) == int(probe.num_rows)
    assert sorted(merged.to_pylist()) == sorted(hashed.to_pylist())


def test_explain_analyze(tmp_path):
    eng = LocalEngine(TpchConnector(0.01))
    out = eng.explain_analyze_sql(
        "select o_orderpriority, count(*) from orders "
        "where o_totalprice > 100000 group by o_orderpriority order by 1")
    assert "rows=5" in out                      # 5 priorities out
    assert "TableScan orders" in out
    assert "fused into parent" in out           # filter fused into agg
    assert "wall" in out and "footprint" in out
    # plain execution still works after (stats toggled off again)
    assert len(eng.execute_sql("select count(*) from orders")) == 1


def test_worker_metrics_endpoint():
    from presto_tpu.server import TpuWorkerServer
    srv = TpuWorkerServer(TpchConnector(0.01)).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/info/metrics",
                timeout=10) as resp:
            text = resp.read().decode()
        assert "presto_tpu_tasks 0" in text
        assert "presto_tpu_uptime_seconds" in text
    finally:
        srv.stop()


def test_worker_consumes_session_properties():
    """A tiny query_max_memory_per_node arriving via the wire session
    must fail the task with MemoryLimitExceeded."""
    from presto_tpu.server import TpuWorkerServer
    from tests.protocol_fixtures import q6_fragment, task_update_request
    from tests.test_worker_http import _await_finish, _post_task

    srv = TpuWorkerServer(TpchConnector(0.01)).start()
    try:
        tur = task_update_request(q6_fragment(0.01), n_splits=1, sf=0.01)
        tur.session.systemProperties = {
            "query_max_memory_per_node": "50kB",
            "some_unknown_coordinator_prop": "x"}
        class W:  # minimal adapter for _post_task
            port = srv.port
        _post_task(W, "mem.0.0.0.0", tur)
        st = _await_finish(W, "mem.0.0.0.0")
        assert st["state"] == "FAILED"
        assert any("MemoryLimitExceeded" in f["message"]
                   for f in st["failures"])
    finally:
        srv.stop()


def test_join_distribution_type_forced():
    """join_distribution_type steers AddExchanges: PARTITIONED forces
    hash exchanges where AUTOMATIC would broadcast a small build, and
    BROADCAST forces replication."""
    from presto_tpu.config import Session
    from presto_tpu.connectors import TpchConnector
    from presto_tpu.plan.fragment import add_exchanges
    from presto_tpu.plan.nodes import ExchangeNode, Partitioning
    from presto_tpu.sql.analyzer import Planner
    from presto_tpu.sql.parser import parse_sql

    conn = TpchConnector(0.01)
    plan = Planner(conn).plan_query(parse_sql(
        "select count(*) from lineitem, nation "
        "where l_suppkey % 25 = n_nationkey"))

    def kinds(p):
        out = []

        def walk(n):
            if isinstance(n, ExchangeNode):
                out.append(n.partitioning)
            for c in n.children():
                if c is not None:
                    walk(c)
        walk(p)
        return out

    auto = kinds(add_exchanges(plan, conn,
                               Session({})))
    part = kinds(add_exchanges(plan, conn, Session(
        {"join_distribution_type": "PARTITIONED"})))
    bc = kinds(add_exchanges(plan, conn, Session(
        {"join_distribution_type": "BROADCAST"})))
    # tiny nation build: AUTOMATIC and BROADCAST replicate...
    assert Partitioning.BROADCAST in auto
    assert Partitioning.BROADCAST in bc
    # ...PARTITIONED must not
    assert Partitioning.BROADCAST not in part
    assert Partitioning.HASH in part


def test_query_max_execution_time_enforced():
    from presto_tpu.config import Session
    from presto_tpu.connectors import TpchConnector
    from presto_tpu.exec import LocalEngine
    from presto_tpu.exec.executor import QueryTimeoutError
    import pytest

    eng = LocalEngine(TpchConnector(0.01), session=Session(
        {"query_max_execution_time": "0.000001"}))
    with pytest.raises(QueryTimeoutError, match="exceeded"):
        # join plan -> island path -> deadline checked between islands
        eng.execute_sql(
            "select count(*) from lineitem, orders "
            "where l_orderkey = o_orderkey")
