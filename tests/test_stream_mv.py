"""Streaming ingest + incrementally maintained materialized views.

Contracts (README "Streaming ingest & materialized views"):

- concurrent `append_rows` writers never lose a `table_version` bump,
  and the per-version watermark history stays monotone in BOTH
  coordinates with an exact cumulative row count at every version;
- the `POST /v1/ingest/{catalog}/{schema}/{table}` front door returns
  commit receipts the seeded StreamDriver verifies as a total order
  (strictly monotone versions, totals growing by exactly the batch),
  and refuses malformed batches with 400 instead of partial appends;
- every REFRESH is oracle-exact against sqlite over the identical
  rows — incremental (watermark delta merge) and full recompute alike,
  across repeated ingest/refresh cycles, with a worker hard-killed
  mid-refresh under retry_policy=TASK, and after a coordinator restart
  that recovered definitions from the MV journal;
- MV state is a pinned fragment-cache entry: cache pressure from
  unpinned traffic cannot evict it, DROP releases it, and a state
  larger than the budget is refused with MVError, not silently
  truncated;
- a corrupt MV journal is moved aside (`started_fresh`) rather than
  recovering garbage definitions, and compaction drops tombstones.
"""

import json
import os
import random
import sqlite3
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from presto_tpu.config import MVConfig, TransportConfig
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.exec import LocalEngine
from presto_tpu.mv.journal import MVJournal
from presto_tpu.mv.manager import MaterializedViewManager, MVError
from presto_tpu.obs.wide_events import LEDGER
from presto_tpu.server.cluster import TpuCluster
from presto_tpu.server.statement import StatementServer
from presto_tpu.server.task_manager import TpuTaskManager
from presto_tpu.stream.watermarks import watermark_store
from presto_tpu.testing.stream import StreamDriver
from presto_tpu.types import DOUBLE, VARCHAR
from tests.oracle import assert_rows_match

FAST = TransportConfig(
    retry_base_backoff_s=0.01, retry_max_backoff_s=0.2,
    retry_budget_s=5.0, breaker_failure_threshold=3,
    breaker_cooldown_s=0.3)

SCHEMA = [("l_returnflag", VARCHAR), ("l_linestatus", VARCHAR),
          ("l_quantity", DOUBLE), ("l_extendedprice", DOUBLE)]

#: inside the incrementally maintainable class: one table, mergeable
#: aggregates (avg decomposes to sum+count), a filter, group keys
MV_SQL = ("select l_returnflag, l_linestatus, count(*), "
          "sum(l_quantity), avg(l_extendedprice), min(l_quantity), "
          "max(l_extendedprice) from lineitem where l_quantity > 5 "
          "group by l_returnflag, l_linestatus")

#: ORDER BY pushes this outside the incremental class — the manager
#: must fall back to full recompute and stay exact anyway
FULL_ONLY_SQL = ("select l_returnflag, count(*) from lineitem "
                 "group by l_returnflag order by l_returnflag")

_FLAGS = ("A", "N", "R")
_STATUSES = ("F", "O")


def _row(rng, _ordinal):
    return (rng.choice(_FLAGS), rng.choice(_STATUSES),
            round(rng.uniform(1.0, 50.0), 2),
            round(rng.uniform(900.0, 105000.0), 2))


def _seeded_conn(n_rows: int, seed: int = 0) -> MemoryConnector:
    conn = MemoryConnector()
    conn.create("lineitem", SCHEMA)
    rng = random.Random(f"{seed}:base")
    conn.append_rows("lineitem",
                     [_row(rng, i) for i in range(n_rows)])
    return conn


def _append_batch(conn, n: int, seed: str) -> int:
    rng = random.Random(seed)
    conn.append_rows("lineitem", [_row(rng, i) for i in range(n)])
    return n


def _host_rows(conn, name):
    """Decode a memory table back to python rows (string codes through
    the table-wide dictionary) for the sqlite oracle load."""
    t = conn.tables[name]
    cols = t.column_names()
    out = []
    for i in range(t.num_rows):
        row = []
        for c in cols:
            v = t.arrays[c][i]
            if t.types[c].is_string:
                row.append(t.dicts[c].words[int(v)])
            else:
                row.append(v.item() if hasattr(v, "item") else v)
        out.append(tuple(row))
    return out


def _sqlite_oracle(conn, sql):
    """sqlite over the identical rows (H2QueryRunner's role)."""
    db = sqlite3.connect(":memory:")
    cols = [c for c, _t in SCHEMA]
    db.execute(f"create table lineitem ({', '.join(cols)})")
    db.executemany(
        f"insert into lineitem values ({', '.join('?' * len(cols))})",
        _host_rows(conn, "lineitem"))
    rows = db.execute(sql).fetchall()
    db.close()
    return [tuple(r) for r in rows]


# ================================================================
# concurrent appends: version and watermark accounting
# ================================================================

def test_concurrent_append_version_accounting():
    """N writer threads, no lost table_version bumps: the final
    version is exactly initial + total batches, and the watermark
    history pairs EVERY version with an exact cumulative row count."""
    conn = MemoryConnector()
    conn.create("t", SCHEMA)
    v0 = conn.table_version("t")
    threads, batches_each, rows_each = 8, 10, 5

    def writer(tid):
        rng = random.Random(f"writer:{tid}")
        for b in range(batches_each):
            conn.append_rows(
                "t", [_row(rng, b * rows_each + i)
                      for i in range(rows_each)])

    ts = [threading.Thread(target=writer, args=(i,),
                           name=f"presto-tpu-test-writer-{i}")
          for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()

    total_batches = threads * batches_each
    total_rows = total_batches * rows_each
    assert conn.table_version("t") == v0 + total_batches, \
        "a concurrent append lost its version bump"
    assert conn.tables["t"].num_rows == total_rows

    hist = watermark_store(conn).snapshot()["t"]
    # one mark per bump: the CREATE plus every append
    assert len(hist) == total_batches + 1
    for (pv, pr), (nv, nr) in zip(hist, hist[1:]):
        assert nv == pv + 1, f"version gap {pv} -> {nv}"
        assert nr == pr + rows_each, f"row-count tear at v{nv}"
    store = watermark_store(conn)
    assert store.latest("t") == (v0 + total_batches, total_rows)
    for v, r in hist:
        assert store.total_rows_at("t", v) == r
    # and the delta proof spans the whole concurrent window
    assert store.delta_range("t", v0, v0 + total_batches) \
        == (0, total_rows)


# ================================================================
# ingest front door
# ================================================================

def test_ingest_endpoint_receipts_and_rejection():
    conn = _seeded_conn(50)
    engine = LocalEngine(conn)
    srv = StatementServer(engine).start()
    try:
        driver = StreamDriver(srv.base, "lineitem", _row, seed=3,
                              batch_min=2, batch_max=9)
        for _ in range(10):
            receipt = driver.step()   # _check_receipt is the oracle
            assert receipt is not None and receipt["rows"] >= 2
        rep = driver.report()
        assert rep["batches"] == 10 and rep["errors"] == 0 \
            and rep["rejected"] == 0
        assert rep["lastTotalRows"] == 50 + rep["rows"]
        assert conn.tables["lineitem"].num_rows == 50 + rep["rows"]

        def post(path, body):
            req = urllib.request.Request(
                srv.base + path, data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        # unknown table refused whole, not partially applied
        code, body = post("/v1/ingest/memory/default/nope",
                          b'{"rows": [[1, 2, 3, 4]]}')
        assert code == 400 and "nope" in body["error"]
        # arity mismatch refused before ANY row lands
        n_before = conn.tables["lineitem"].num_rows
        code, _body = post("/v1/ingest/memory/default/lineitem",
                           b'{"rows": [["A", "F", 1.0, 2.0], ["A"]]}')
        assert code == 400
        assert conn.tables["lineitem"].num_rows == n_before
        # malformed body
        code, _body = post("/v1/ingest/memory/default/lineitem",
                           b'{"rows": 7}')
        assert code == 400
    finally:
        srv.stop()


# ================================================================
# refresh exactness: incremental and full, many cycles
# ================================================================

def test_refresh_oracle_exact_across_cycles():
    conn = _seeded_conn(2000)
    engine = LocalEngine(conn)
    engine.execute_sql(f"create materialized view agg as {MV_SQL}")
    mgr = engine.mv_manager

    def stat(name):
        return next(s for s in mgr.stats() if s["name"] == name)

    # first refresh materializes with a version-pinned full rebuild
    (scanned,) = engine.execute_sql("refresh materialized view agg")[0]
    assert scanned == 2000
    assert stat("agg")["last_refresh_kind"] == "full"
    assert stat("agg")["incremental_capable"] == 1 or \
        stat("agg")["incremental_capable"] is True
    assert_rows_match(mgr.rows("agg"), _sqlite_oracle(conn, MV_SQL),
                      sort=True)

    for cycle in range(3):
        n = _append_batch(conn, 150 + 10 * cycle, f"cycle:{cycle}")
        assert stat("agg")["staleness_seconds"] > 0.0
        (scanned,) = engine.execute_sql(
            "refresh materialized view agg")[0]
        assert scanned == n, "delta scan read more than the append"
        s = stat("agg")
        assert s["last_refresh_kind"] == "incremental"
        assert s["last_delta_rows"] == n
        assert s["staleness_seconds"] == 0.0
        assert_rows_match(mgr.rows("agg"),
                          _sqlite_oracle(conn, MV_SQL), sort=True)

    # unchanged base: a no-op incremental refresh scanning zero rows
    (scanned,) = engine.execute_sql("refresh materialized view agg")[0]
    assert scanned == 0
    assert stat("agg")["last_refresh_kind"] == "incremental"


def test_ineligible_query_full_recompute_stays_exact():
    conn = _seeded_conn(800)
    engine = LocalEngine(conn)
    engine.execute_sql(
        f"create materialized view ordered as {FULL_ONLY_SQL}")
    mgr = engine.mv_manager
    s = next(x for x in mgr.stats() if x["name"] == "ordered")
    assert not s["incremental_capable"]
    for cycle in range(2):
        engine.execute_sql("refresh materialized view ordered")
        s = next(x for x in mgr.stats() if x["name"] == "ordered")
        assert s["last_refresh_kind"] == "full"
        assert_rows_match(mgr.rows("ordered"),
                          _sqlite_oracle(conn, FULL_ONLY_SQL),
                          sort=True)
        _append_batch(conn, 120, f"ord:{cycle}")
    engine.execute_sql("drop materialized view ordered")


def test_lifecycle_error_semantics():
    conn = _seeded_conn(60)
    engine = LocalEngine(conn)
    mgr = MaterializedViewManager(conn, run_sql=engine.execute_sql)
    assert mgr.create("v", MV_SQL)
    with pytest.raises(MVError, match="already exists"):
        mgr.create("v", MV_SQL)
    assert mgr.create("v", MV_SQL, if_not_exists=True) is False
    with pytest.raises(MVError, match="not been refreshed"):
        mgr.rows("v")
    with pytest.raises(MVError, match="unknown"):
        mgr.refresh("ghost")
    with pytest.raises(MVError, match="unknown"):
        mgr.drop("ghost")
    assert mgr.drop("ghost", if_exists=True) is False
    assert mgr.drop("v")
    assert mgr.names() == []


# ================================================================
# pinned state vs cache pressure
# ================================================================

def test_mv_state_survives_cache_pressure_and_drop_releases():
    conn = _seeded_conn(500)
    engine = LocalEngine(conn)
    mgr = MaterializedViewManager(
        conn, run_sql=engine.execute_sql,
        config=MVConfig(state_budget_bytes=1 << 20))
    mgr.create("pinned", MV_SQL)
    mgr.refresh("pinned")
    before = mgr.rows("pinned")
    assert mgr.cache.pinned_bytes > 0
    # unpinned traffic worth 4x the budget churns through the cache
    for i in range(64):
        mgr.cache.put(f"filler:{i}", [np.zeros(64 << 10, np.uint8)])
    assert mgr.cache.evictions > 0, "pressure never evicted anything"
    assert mgr.rows("pinned") == before, \
        "cache pressure evicted pinned MV state"
    mgr.drop("pinned")
    assert mgr.cache.pinned_bytes == 0, "DROP leaked pinned budget"


def test_mv_state_over_budget_is_refused():
    conn = _seeded_conn(200)
    engine = LocalEngine(conn)
    mgr = MaterializedViewManager(
        conn, run_sql=engine.execute_sql,
        config=MVConfig(state_budget_bytes=64))
    mgr.create("big", MV_SQL)
    with pytest.raises(MVError, match="state budget"):
        mgr.refresh("big")


# ================================================================
# chaos: worker hard-killed mid-refresh under retry_policy=TASK
# ================================================================

def test_refresh_exact_across_worker_kill_task_retry(monkeypatch):
    """Hard-kill a worker while the incremental delta query is in
    flight under retry_policy=TASK: recovery re-runs the lost task,
    the merged state stays oracle-exact (no double count, no tear),
    and the REFRESH statement still emits exactly ONE wide event
    carrying the mv block."""
    conn = _seeded_conn(1500)
    c = TpuCluster(
        conn, n_workers=2,
        session_properties={"query_max_execution_time": "120",
                            "retry_policy": "TASK"},
        transport_config=FAST)
    try:
        c.execute_sql(f"create materialized view chaos as {MV_SQL}")
        c.execute_sql("refresh materialized view chaos")
        mgr = c.mv_manager
        _append_batch(conn, 400, "chaos:delta")

        victim = c.workers[1].task_manager.node_id
        orig = TpuTaskManager._run_inner
        executed = []
        on_victim = threading.Event()
        killed = threading.Event()

        def spy(self, task):
            executed.append(
                (self.node_id, int(task.task_id.rsplit(".", 1)[1])))
            if self.node_id == victim:
                on_victim.set()
                # hold the victim's work until the kill has landed,
                # however long this thread's rival takes to get there
                killed.wait(timeout=60)
                # and a killed process runs nothing more. Here every
                # worker is a thread of one process, and since the
                # workers keep their programs (PR 32) this task would
                # take milliseconds: it could commit its spool before
                # the coordinator looks, and recovery would absorb that
                # (legal) where this test wants the re-run
                return None
            return orig(self, task)

        monkeypatch.setattr(TpuTaskManager, "_run_inner", spy)
        LEDGER.clear()
        sql = "refresh materialized view chaos"
        results, errors = [], []

        def run():
            try:
                results.append(c.execute_sql(sql))
            except Exception as e:   # noqa: BLE001 — collected below
                errors.append(e)

        t = threading.Thread(target=run, name="mv-chaos-refresh",
                             daemon=True)
        t.start()
        assert on_victim.wait(timeout=30), \
            "victim never executed a task"
        from tests.test_elastic import _hard_kill
        _hard_kill(c.workers[1])
        killed.set()
        t.join(timeout=120)
        assert not t.is_alive(), "refresh wedged across the kill"
        assert not errors, f"refresh failed despite recovery: {errors}"
        assert any(a > 0 for _n, a in executed), \
            "kill never produced an attempt>0 (recovery) execution"

        s = next(x for x in mgr.stats() if x["name"] == "chaos")
        assert s["last_refresh_kind"] == "incremental"
        assert s["last_delta_rows"] == 400
        assert_rows_match(mgr.rows("chaos"),
                          _sqlite_oracle(conn, MV_SQL), sort=True)

        evs = [e for e in LEDGER.snapshot() if e.get("query") == sql]
        assert len(evs) == 1, \
            f"recovery duplicated the refresh wide event: {len(evs)}"
        mv = evs[0]["mv"]
        assert mv is not None and mv["view"] == "chaos"
        assert mv["kind"] == "incremental" and mv["deltaRows"] == 400
    finally:
        c.stop()


# ================================================================
# coordinator restart: journal recovery
# ================================================================

def test_coordinator_restart_recovers_definitions(tmp_path):
    """Definitions survive a coordinator restart through the MV
    journal; state does NOT (it is process-local pinned cache), so the
    first post-restart refresh is a full rebuild — and exact."""
    conn = _seeded_conn(800)
    jp = str(tmp_path / "mv.journal")
    c1 = TpuCluster(conn, n_workers=1, transport_config=FAST,
                    mv_journal_path=jp)
    try:
        c1.execute_sql(f"create materialized view surv as {MV_SQL}")
        c1.execute_sql("refresh materialized view surv")
        c1.execute_sql(
            f"create materialized view doomed as {FULL_ONLY_SQL}")
        c1.execute_sql("drop materialized view doomed")
        before = c1.mv_manager.rows("surv")
    finally:
        c1.stop()

    c2 = TpuCluster(conn, n_workers=1, transport_config=FAST,
                    mv_journal_path=jp)
    try:
        mgr = c2.mv_manager
        assert mgr.names() == ["surv"], \
            "tombstoned view resurrected or definition lost"
        s = next(x for x in mgr.stats() if x["name"] == "surv")
        assert s["recovered"], "restart did not mark the view recovered"
        with pytest.raises(MVError, match="not been refreshed"):
            mgr.rows("surv")     # state died with the old process
        c2.execute_sql("refresh materialized view surv")
        s = next(x for x in mgr.stats() if x["name"] == "surv")
        assert s["last_refresh_kind"] == "full", \
            "recovered view merged a delta against dead state"
        assert not s["recovered"]
        assert mgr.rows("surv") == before
        assert_rows_match(mgr.rows("surv"),
                          _sqlite_oracle(conn, MV_SQL), sort=True)
        # the registry is queryable with the cluster's own SQL
        rows = c2.execute_sql(
            "select name, incremental_capable, refreshes "
            "from system.runtime.materialized_views")
        assert rows == [("surv", 1, 1)]
    finally:
        c2.stop()


# ================================================================
# journal units: corruption, compaction
# ================================================================

def test_corrupt_journal_moved_aside_starts_fresh(tmp_path):
    jp = str(tmp_path / "mv.journal")
    with open(jp, "w") as f:
        f.write('{"name": "x", "sql": "select 1", "state": "live"}\n'
                '{"nam')          # torn final write
    conn = _seeded_conn(40)
    engine = LocalEngine(conn)
    mgr = MaterializedViewManager(conn, run_sql=engine.execute_sql,
                                  journal_path=jp)
    assert mgr.journal.started_fresh
    assert mgr.names() == [], "recovered definitions from a corrupt log"
    assert os.path.exists(jp + ".corrupt"), "evidence discarded"
    # and the path is writable again: create journals normally
    mgr.create("v", MV_SQL)
    assert [r["name"] for r in MVJournal(jp).live()] == ["v"]


def test_journal_merge_and_compaction(tmp_path):
    jp = str(tmp_path / "mv.journal")
    j = MVJournal(jp, compact_threshold=1000)
    j.append("a", sql="select 1", state="live")
    j.append("b", sql="select 2", state="live")
    j.append("a", versions={"t": 4}, last_kind="incremental")
    j.append("b", state="dropped")
    # later lines merge over earlier ones per name
    live = MVJournal(jp).live()
    assert [r["name"] for r in live] == ["a"]
    assert live[0]["versions"] == {"t": 4} \
        and live[0]["last_kind"] == "incremental"
    j.compact()
    with open(jp) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    assert len(lines) == 1, "compaction kept tombstones"
    assert json.loads(lines[0])["name"] == "a"
