"""Adaptive history-based optimization (reference: Presto@Meta VLDB'23
HistoryBasedPlanStatisticsCalculator + ReorderJoins + dynamic filtering):

  - HistoryStore persistence discipline (crash-safe atomic save, bounded
    eviction, corrupt-file-starts-fresh);
  - q03/q18 plan-shape regressions: every inner join keeps its smaller
    estimated side on the hash build, and seeded history flips the
    decision (the rule plans from measurements, not the FK guess);
  - cluster-fed HBO: the coordinator folds worker-reported actuals into
    its HistoryStore so the second run of a query plans from history;
  - cross-exchange dynamic filtering: the build fragment's key domain
    prunes probe-side scan splits, oracle-exact, including under the
    kill-build-worker chaos case (filter lost degrades to an unfiltered
    scan, never wrong rows).
"""

import sqlite3
import threading
import time

import pytest

from oracle import table_df
from presto_tpu.config import Session, TransportConfig
from presto_tpu.connectors import TpchConnector
from presto_tpu.exec import LocalEngine
from presto_tpu.plan.iterative import reorder_joins
from presto_tpu.plan.nodes import JoinNode, JoinType, TableScanNode
from presto_tpu.plan.stats import (
    HistoryStore, canonical_key, estimate_rows,
)
from presto_tpu.server.cluster import TpuCluster
from presto_tpu.server.task_manager import _M_DF_PRUNED
from tpch_queries import QUERIES

SF = 0.01

#: probe side is orders; the build is a filtered derived table so the
#: build fragment's key domain is small (9 customers at SF 0.01) and the
#: coordinator can push an IN constraint into the orders scan splits
DF_SQL = (
    "select o_orderkey, o_totalprice from orders join "
    "(select c_custkey from customer where c_acctbal < -900) t "
    "on o_custkey = c_custkey order by o_orderkey")

#: tight retry windows so the chaos kill resolves in test time
CHAOS_TRANSPORT = TransportConfig(
    retry_base_backoff_s=0.01, retry_max_backoff_s=0.2,
    retry_budget_s=5.0, breaker_failure_threshold=3,
    breaker_cooldown_s=0.3)


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(SF)


def _joins(plan):
    out = []

    def walk(n):
        if isinstance(n, JoinNode):
            out.append(n)
        for c in n.children():
            if c is not None:
                walk(c)
    walk(plan)
    return out


def _scan_tables(n):
    out = []

    def walk(m):
        if isinstance(m, TableScanNode):
            out.append(m.table)
        for c in m.children():
            if c is not None:
                walk(c)
    walk(n)
    return out


def _df_oracle(conn):
    """sqlite over the identical generated rows (H2QueryRunner's role)."""
    db = sqlite3.connect(":memory:")
    for t in ("customer", "orders"):
        table_df(conn, t).to_sql(t, db, index=False)
    rows = db.execute(DF_SQL).fetchall()
    db.close()
    return [(int(k), float(p)) for k, p in rows]


# ------------------------------------------------------- HistoryStore

def test_history_round_trip(tmp_path):
    p = str(tmp_path / "hbo.json")
    h = HistoryStore(p)
    h.record("aaa", 7)
    h.record("bbb", 12345)
    h.record("aaa", 9)          # re-record wins
    h.save()
    h2 = HistoryStore(p)
    assert h2.rows == {"bbb": 12345, "aaa": 9}
    assert h2.get("aaa") == 9 and h2.hits == 1
    assert h2.get("zzz") is None and h2.misses == 1


def test_history_corrupt_file_starts_fresh(tmp_path):
    p = str(tmp_path / "hbo.json")
    with open(p, "w") as f:
        f.write('{"trunc')
    h = HistoryStore(p)
    assert h.rows == {}
    h.record("k", 3)
    h.save()                    # and the path is writable again
    assert HistoryStore(p).get("k") == 3


def test_history_bounded_eviction():
    h = HistoryStore(max_entries=10)
    for i in range(25):
        h.record(f"k{i}", i)
    assert len(h.rows) == 10
    assert h.get("k0") is None          # oldest evicted
    assert h.get("k24") == 24           # newest kept
    h.record("k15", 99)                 # move-to-end on re-record
    h.record("knew", 1)
    assert h.get("k15") == 99


def test_history_save_is_atomic(tmp_path):
    """No temp droppings, and the file is complete JSON after save."""
    import json
    import os

    p = str(tmp_path / "sub" / "hbo.json")
    h = HistoryStore(p)
    h.record("k", 1)
    h.save()
    assert sorted(os.listdir(os.path.dirname(p))) == ["hbo.json"]
    with open(p) as f:
        assert json.load(f) == {"k": 1}


# -------------------------------------------- join reordering (q03/q18)

@pytest.mark.parametrize("qid", [3, 18])
def test_plan_shape_small_side_builds(conn, qid):
    """Every inner join in the q03/q18 plans keeps the smaller estimated
    side on the hash build — the analyzer's greedy order already does
    this, and ReorderJoins must agree (fire count 0, shape unchanged)."""
    eng = LocalEngine(conn, session=Session(
        {"join_reordering_enabled": "false"}))
    raw = eng.plan_sql(QUERIES[qid])
    for j in _joins(raw):
        if j.join_type == JoinType.INNER:
            assert estimate_rows(j.build, conn) <= \
                estimate_rows(j.probe, conn), \
                f"q{qid}: build side estimated larger than probe"
    out, fired = reorder_joins(raw, conn)
    assert fired == 0
    assert [_scan_tables(j.build) for j in _joins(out)] == \
        [_scan_tables(j.build) for j in _joins(raw)]


def test_q03_history_flips_build_side(conn):
    """Seeded history claiming the customer build is huge makes the rule
    commute the top join (customer becomes the probe), and the reordered
    plan still returns identical rows."""
    raw_eng = LocalEngine(conn, session=Session(
        {"join_reordering_enabled": "false"}))
    raw = raw_eng.plan_sql(QUERIES[3])
    top = _joins(raw)[0]
    assert _scan_tables(top.build) == ["customer"]

    hist = HistoryStore()
    hist.record(canonical_key(top.build), 10_000_000)
    hist.record(canonical_key(top.probe), 100)
    out, fired = reorder_joins(raw, conn, hist)
    assert fired == 1
    assert _scan_tables(_joins(out)[0].probe) == ["customer"]

    seeded = LocalEngine(conn, history=hist)
    assert seeded.execute_sql(QUERIES[3]) == \
        raw_eng.execute_sql(QUERIES[3])
    assert seeded.last_join_reorders == 1


def test_reorder_skips_non_inner(conn):
    """SEMI joins (the q18 IN-subquery shape) are never commuted, even
    when history claims the build side dwarfs the probe."""
    raw = LocalEngine(conn, session=Session(
        {"join_reordering_enabled": "false"})).plan_sql(QUERIES[18])
    semis = [j for j in _joins(raw) if j.join_type == JoinType.SEMI]
    assert semis
    hist = HistoryStore()
    for j in semis:
        hist.record(canonical_key(j.build), 10_000_000)
        hist.record(canonical_key(j.probe), 1)
    out, fired = reorder_joins(raw, conn, hist)
    assert fired == 0


def test_second_run_uses_history_local(conn):
    """Local path: after one executed run the re-planned equivalent node
    estimates its OBSERVED rows (estimate equals recorded actual)."""
    hist = HistoryStore()
    eng = LocalEngine(conn, session=Session({"collect_stats": "true"}),
                      history=hist)
    sql = ("select count(*) from customer, orders "
           "where c_custkey = o_custkey")
    eng.execute_sql(sql)
    assert hist.rows, "execution recorded no history"
    join = _joins(eng.plan_sql(sql))[0]
    recorded = hist.get(canonical_key(join.build))
    if recorded is not None:
        assert estimate_rows(join.build, conn, hist) == \
            float(max(recorded, 1))


# -------------------------------------------------- cluster: HBO + DF

@pytest.fixture(scope="module")
def cluster(conn):
    # the probe stage waits for the build's key domain as long as it
    # takes (the wait ends when the build finishes): these tests are
    # about the pruning, not about the 400 ms a production session
    # gives up after, which six test workers on one machine outlast
    c = TpuCluster(conn, n_workers=2, session_properties={
        "dynamic_filter_wait_ms": "300000"})
    yield c
    c.stop()


def test_cluster_second_run_uses_history(cluster):
    sql = ("select count(*) from customer, orders "
           "where c_custkey = o_custkey")
    first = cluster.execute_sql(sql)
    assert cluster.history.rows, \
        "coordinator folded no worker actuals into the HistoryStore"
    assert cluster.execute_sql(sql) == first
    assert cluster.last_hbo["hits"] > 0, \
        "second planning answered nothing from history"


def test_cluster_dynamic_filter_prunes_oracle_exact(cluster, conn):
    before = _M_DF_PRUNED.value()
    got = cluster.execute_sql(DF_SQL)
    pruned = _M_DF_PRUNED.value() - before
    assert pruned > 0, "cross-exchange dynamic filter pruned nothing"
    assert [(int(k), float(p)) for k, p in got] == _df_oracle(conn)


def test_cluster_dynamic_filter_disabled_still_exact(cluster, conn):
    old = dict(cluster.session_properties)
    cluster.session_properties["dynamic_filtering_enabled"] = "false"
    try:
        before = _M_DF_PRUNED.value()
        got = cluster.execute_sql(DF_SQL + " limit 100000")
        assert _M_DF_PRUNED.value() == before
        assert [(int(k), float(p)) for k, p in got] == _df_oracle(conn)
    finally:
        cluster.session_properties.clear()
        cluster.session_properties.update(old)


def test_cluster_explain_analyze_hbo_line(cluster):
    out = cluster.explain_analyze_sql(DF_SQL)
    assert "HBO: hits=" in out
    assert "dynamic_filter_rows_pruned=" in out
    assert "est_rows=" in out   # history-known operators annotated


def test_local_explain_analyze_est_rows(conn):
    out = LocalEngine(conn).explain_analyze_sql(
        "select count(*) from orders where o_orderkey < 100")
    assert "est_rows=" in out


def test_dynamic_filter_chaos_kill_build_worker(conn):
    """Build worker killed mid-query under retry_policy=TASK: the lost
    dynamic filter degrades to an unfiltered probe scan and recovery
    re-runs the lost tasks — rows stay oracle-exact, never wrong."""
    want = _df_oracle(conn)
    c = TpuCluster(conn, n_workers=3,
                   session_properties={"retry_policy": "TASK"},
                   transport_config=CHAOS_TRANSPORT)
    try:
        assert [(int(k), float(p))
                for k, p in c.execute_sql(DF_SQL)] == want
        killer = threading.Timer(0.05, c.workers[1].stop)
        killer.start()
        try:
            got = c.execute_sql(DF_SQL)
        finally:
            killer.cancel()
        assert [(int(k), float(p)) for k, p in got] == want
        # and again with the worker definitely gone the whole query
        time.sleep(0.1)
        got = c.execute_sql(DF_SQL)
        assert [(int(k), float(p)) for k, p in got] == want
    finally:
        c.stop()


# ------------------------- a filtering semi join learns its capacity (q18)

SEMI_SQL = (
    "select o_orderkey, o_totalprice from orders where o_orderkey {op} "
    "(select l_orderkey from lineitem group by l_orderkey "
    "having sum(l_quantity) > {q})")


def _rows(page):
    return sorted(page.to_pylist())


@pytest.mark.parametrize("op, q, n_rows, learned", [
    ("in", 250, 79, 256), ("not in", 20, 1048, 4096)],
    ids=["semi", "anti"])
def test_filtering_semi_join_learns_its_survivors_capacity(
        conn, tmp_path, monkeypatch, op, q, n_rows, learned):
    """TPC-H Q18's shape: the SEMI join keeps 79 of 15,000 orders (and an
    ANTI join, through the same lines, 1,048). Its output starts at the
    probe's capacity, anneals to the bucket of what survived, so what
    runs above it is not paid at the probe's size; a learned capacity
    that is too small re-runs through the overflow loop and answers the
    same."""
    from presto_tpu.exec.executor import Executor
    monkeypatch.setenv("PRESTO_TPU_CAPS_CACHE", str(tmp_path / "caps.json"))
    engine = LocalEngine(conn)
    ex = Executor(conn)
    plan = engine.plan_sql(SEMI_SQL.format(op=op, q=q))
    first = ex.execute(plan)
    assert int(first.num_rows) == n_rows and first.capacity == 16384
    second = ex.execute(plan)
    assert int(second.num_rows) == n_rows and second.capacity == learned
    assert _rows(second) == _rows(first)
    # another executor (a later task) starts from the persisted capacity
    third = Executor(conn).execute(plan)
    assert third.capacity == learned and _rows(third) == _rows(first)

    if op == "in":
        # the threshold is an input of the programs (expr/params.py), so
        # 200 runs what 250 learned: 904 survivors against a learned
        # capacity of 256, the overflow loop re-runs at the bucket of
        # what was needed, and an executor that never learned (a caps
        # file of its own) gives the same rows
        less_selective = engine.plan_sql(SEMI_SQL.format(op=op, q=200))
        got = ex.execute(less_selective)
        assert int(got.num_rows) == 904 and got.capacity == 1024
        monkeypatch.setenv("PRESTO_TPU_CAPS_CACHE",
                           str(tmp_path / "unlearned.json"))
        want = Executor(conn).execute(less_selective)
        assert want.capacity == 16384 and _rows(got) == _rows(want)
        # the capacity holds the peak of the values seen: 250 again runs
        # at 1,024 slots and lowers nothing
        again = ex.execute(plan)
        assert again.capacity == 1024 and _rows(again) == _rows(first)
