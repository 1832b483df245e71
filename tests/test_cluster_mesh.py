"""Cluster mesh execution tier (server/mesh_tier.py): mesh-lowered
worker tasks + ICI-backed repartition exchange.

The contract under test, end to end through `TpuCluster.execute_sql`:

  - co-locatable join/agg plans (TPCH q03/q18) fuse onto ONE mesh
    worker, their inter-stage exchanges lower to real ICI collectives
    (`mesh_ici_exchange_bytes_total` grows), and the rows stay EXACT
    against an independent sqlite oracle;
  - killing the chosen mesh worker mid-query under retry_policy=TASK
    degrades to the HTTP/spool recovery path and still produces
    oracle-exact rows (seed matrix, same FaultInjector discipline as
    tests/test_spool_chaos.py);
  - a non-co-located control (MeshTierConfig(colocate=False)) moves
    ZERO bytes over ICI while answers stay correct;
  - a draining worker (PR 10 sequence) retracts its mesh advertisement
    and is never chosen by placement;
  - the ndev==1 guards in parallel/dist.py keep the dist executor
    usable on a single-device mesh (no mesh axis to collect over).
"""

import datetime
import math
import re
import sqlite3
import time

import pytest

from presto_tpu.config import MeshTierConfig, TransportConfig
from presto_tpu.connectors import TpchConnector
from presto_tpu.protocol import transport as _transport
from presto_tpu.server import mesh_tier
from presto_tpu.server.cluster import TpuCluster
from presto_tpu.spool.store import spool_counters
from presto_tpu.testing import FaultInjector, FaultSpec
from tests.tpch_queries import QUERIES

SF = 0.01
DEADLINE_S = 120.0

#: the co-location acceptance queries: both join+agg bearing, q18
#: additionally carries a grouped-HAVING IN-subquery (two scans of
#: lineitem in one fused fragment — the duplicate-split regression)
MESH_QUERIES = (3, 18)

#: cheap join+agg for the control/explain tests — mesh-eligible but
#: compile-light (same shape test_spool_chaos.py uses)
SMALL_SQL = ("select r_name, count(*) from nation, region "
             "where n_regionkey = r_regionkey group by r_name "
             "order by r_name")

CHAOS_TRANSPORT = TransportConfig(
    retry_base_backoff_s=0.01, retry_max_backoff_s=0.2,
    retry_budget_s=5.0, breaker_failure_threshold=3,
    breaker_cooldown_s=0.3)

KILL_AFTER = (5, 12, 20, 30, 45)


def _rewrite_dates(sql: str) -> str:
    """sqlite has no `date 'Y-M-D'` literal and the engine stores DATE
    as epoch-day ints — rewrite literals so one SQL text runs on both."""
    def rep(m):
        d = datetime.date(int(m.group(1)), int(m.group(2)),
                          int(m.group(3)))
        return str((d - datetime.date(1970, 1, 1)).days)
    return re.sub(r"date '(\d+)-(\d+)-(\d+)'", rep, sql)


@pytest.fixture(scope="module")
def cluster():
    c = TpuCluster(
        TpchConnector(SF), n_workers=3,
        session_properties={"query_max_execution_time": str(DEADLINE_S),
                            "retry_policy": "TASK",
                            "cluster_mesh_enabled": "true"},
        transport_config=CHAOS_TRANSPORT)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def oracle():
    """Independent sqlite oracle over the same connector data — a mesh
    bug that corrupts rows deterministically would poison any
    cluster-produced baseline."""
    conn = TpchConnector(SF)
    db = sqlite3.connect(":memory:")
    for name in ("customer", "orders", "lineitem", "nation", "region"):
        page = conn.table(name).page()
        cols = list(page.names)
        db.execute(f"create table {name} ({', '.join(cols)})")
        db.executemany(
            f"insert into {name} values "
            f"({', '.join('?' * len(cols))})", page.to_pylist())
    db.commit()
    want = {q: db.execute(_rewrite_dates(QUERIES[q])).fetchall()
            for q in MESH_QUERIES}
    want[SMALL_SQL] = db.execute(SMALL_SQL).fetchall()
    db.close()
    return want


def _assert_rows_match(got, want, ctx=""):
    assert len(got) == len(want), \
        f"{ctx}: {len(got)} rows, oracle has {len(want)}"
    for g, w in zip(got, want):
        assert len(g) == len(w), f"{ctx}: row arity {g} vs {w}"
        for gc, wc in zip(g, w):
            if isinstance(wc, float) or isinstance(gc, float):
                assert math.isclose(gc, wc, rel_tol=1e-6,
                                    abs_tol=1e-9), \
                    f"{ctx}: {g} vs oracle {w}"
            else:
                assert gc == wc, f"{ctx}: {g} vs oracle {w}"


# ---------------------------------------------------------------------------
# tentpole acceptance: q03/q18 mesh-lowered, ICI bytes > 0, oracle-exact
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q", MESH_QUERIES)
def test_mesh_lowered_query_is_oracle_exact(cluster, oracle, q):
    ici0 = mesh_tier.ici_bytes_total()
    got = [tuple(r) for r in cluster.execute_sql(QUERIES[q])]
    ici = mesh_tier.ici_bytes_total() - ici0
    _assert_rows_match(got, oracle[q], ctx=f"q{q:02d}")
    # the plan actually rode the mesh: the coordinator recorded a
    # co-location and the exchange bytes moved over ICI, not HTTP
    cm = cluster.last_cluster_mesh
    assert cm is not None, "query did not take the cluster-mesh path"
    assert cm["ndev"] >= 2 and cm["colocated_stages"] >= 1, cm
    assert ici > 0 and cm["ici_bytes"] > 0, (ici, cm)
    assert cm["fallbacks"] == 0, cm


def test_explain_analyze_reports_mesh_placement(cluster, oracle):
    out = cluster.explain_analyze_sql(SMALL_SQL)
    mesh = [ln for ln in out.splitlines()
            if ln.strip().startswith("Mesh: cluster=true")]
    assert len(mesh) == 1, out
    assert "worker=http://" in mesh[0]
    assert "colocated_stages=" in mesh[0] and "ici_bytes=" in mesh[0]


def test_worker_mesh_surface(cluster):
    """GET /v1/mesh advertisement + the clusterMesh status block + the
    four tier metrics on the process registry."""
    from presto_tpu.obs.metrics import REGISTRY
    for uri in cluster.all_worker_uris:
        adv = cluster.http.request(f"{uri}/v1/mesh").json()
        assert adv["advertising"] is True
        assert int(adv["meshDevices"]) >= 1
        status = cluster.http.request(f"{uri}/v1/status").json()
        blk = status["clusterMesh"]
        assert blk["advertising"] is True
        assert "iciExchangeBytes" in blk and "fallbacks" in blk
    dump = REGISTRY.render()
    for name in ("presto_tpu_mesh_cluster_tasks_total",
                 "presto_tpu_mesh_ici_exchange_bytes_total",
                 "presto_tpu_mesh_exchange_fallback_total",
                 "presto_tpu_mesh_colocated_stages"):
        assert name in dump, name


# ---------------------------------------------------------------------------
# chaos: kill the chosen mesh worker mid-query (retry_policy=TASK)
# ---------------------------------------------------------------------------
def _stabilize(cluster, deadline_s: float = 120.0):
    # polls for the re-admission itself; the bound only ends a wedge
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if len(cluster.check_workers()) == len(cluster.all_worker_uris):
            return
        time.sleep(0.1)
    raise AssertionError(
        f"workers not re-admitted after faults cleared: "
        f"dead={sorted(cluster.dead)}")


@pytest.mark.parametrize("seed", range(5))
def test_kill_mesh_worker_mid_query_stays_exact(cluster, oracle, seed):
    """Hard-kill the worker the placement chose, mid-ICI-exchange: the
    query must degrade to the HTTP/spool recovery path (or re-place the
    fused task on a surviving mesh) and still return ORACLE-EXACT rows
    within the deadline — the tier may lose its speedup, never the
    answer."""
    sql = QUERIES[3]
    # learn the placement with no faults armed so the kill targets the
    # actual mesh worker, not an arbitrary host
    _assert_rows_match([tuple(r) for r in cluster.execute_sql(sql)],
                       oracle[3], ctx=f"seed {seed} pre-kill")
    assert cluster.last_cluster_mesh is not None
    victim = cluster.last_cluster_mesh["worker"].split("://", 1)[1]
    shared = _transport.get_client()

    def run_once(kill_after) -> None:
        inj = FaultInjector(seed=seed,
                            spec=FaultSpec(
                                kill_after={victim: kill_after}),
                            only_hosts={victim})
        cluster.http.fault_injector = inj
        shared.fault_injector = inj
        try:
            start = time.monotonic()
            got = [tuple(r) for r in cluster.execute_sql(sql)]
            # the engine's own clock (`query_max_execution_time`)
            # bounds every await of the query, and a waited-out await
            # is followed by at most one whole-query retry on the
            # survivors: two deadlines, not one, are what a rung may
            # take. Under six test workers a rung has read 184-186 s,
            # rows exact (CHANGES.md PR 25); what held its first await
            # for the whole deadline was not reproduced at PR 31
            # (ROADMAP C13) and is not this assertion's to hide: it
            # still fails a query that needs a third deadline
            assert time.monotonic() - start < 2 * DEADLINE_S + 60, \
                f"seed {seed}: mesh-kill query exceeded deadline"
            _assert_rows_match(got, oracle[3],
                               ctx=f"seed {seed} mesh kill")
        finally:
            cluster.http.fault_injector = None
            shared.fault_injector = None
            inj.revive(victim)
            _stabilize(cluster)

    # the kill ordinal is request-count based and the fused plan sends
    # the victim only a handful of requests (probe, post, status polls,
    # page pull) — a large ordinal never fires at all. Re-arm down a
    # ladder of earlier protocol phases until the death lands
    # mid-flight and recovery engages; every attempt must return exact
    # rows regardless of where the kill lands.
    before = spool_counters()["recoveries"]
    engaged = False
    for kill_after in (KILL_AFTER[seed], 14, 10, 8, 6, 5, 4, 3, 2):
        run_once(kill_after)
        if spool_counters()["recoveries"] - before >= 1:
            engaged = True
            break
    assert engaged, \
        f"seed {seed}: mesh-worker kill never triggered recovery"


# ---------------------------------------------------------------------------
# non-co-located control: zero ICI bytes, correct rows
# ---------------------------------------------------------------------------
def test_non_colocated_control_moves_zero_ici_bytes(oracle):
    c = TpuCluster(
        TpchConnector(SF), n_workers=2,
        session_properties={"query_max_execution_time": str(DEADLINE_S),
                            "cluster_mesh_enabled": "true"},
        mesh_config=MeshTierConfig(colocate=False))
    try:
        ici0 = mesh_tier.ici_bytes_total()
        fb0 = mesh_tier.fallbacks_total()
        got = [tuple(r) for r in c.execute_sql(SMALL_SQL)]
        _assert_rows_match(got, oracle[SMALL_SQL], ctx="control")
        assert mesh_tier.ici_bytes_total() - ici0 == 0
        assert c.last_cluster_mesh is None
        # the declined co-location is accounted, not silent
        assert mesh_tier.fallbacks_total() - fb0 >= 1
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# drain: a SHUTTING_DOWN worker retracts its slice and is never placed
# ---------------------------------------------------------------------------
def test_draining_worker_stops_advertising_mesh():
    c = TpuCluster(
        TpchConnector(SF), n_workers=2,
        session_properties={"cluster_mesh_enabled": "true"})
    try:
        uris = list(c.all_worker_uris)
        w0 = c.workers[0]
        assert w0.task_manager.mesh_tier.advertising()
        assert w0.task_manager.mesh_tier.announce_properties() != {}

        w0.task_manager.drain(timeout_s=5.0)
        adv = c.http.request(f"{uris[0]}/v1/mesh").json()
        assert adv["advertising"] is False and adv["meshDevices"] == 0
        assert w0.task_manager.mesh_tier.announce_properties() == {}

        # placement probes FRESH and must route around the drained slice
        plan = c.plan_sql(SMALL_SQL)
        mp = mesh_tier.plan_cluster_mesh(c, plan, 2)
        assert mp is not None and mp["worker"] == uris[1], mp

        # with every slice drained there is no mesh plan at all — the
        # query keeps the HTTP path and the decline is accounted
        c.workers[1].task_manager.drain(timeout_s=5.0)
        fb0 = mesh_tier.fallbacks_total()
        assert mesh_tier.plan_cluster_mesh(c, plan, 2) is None
        assert mesh_tier.fallbacks_total() - fb0 >= 1
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# ndev==1 guards: the dist executor on a single-device mesh
# ---------------------------------------------------------------------------
def test_dist_executor_single_device_mesh():
    """parallel/dist.py's collective kernels must not touch the mesh
    axis when ndev == 1 (there is none to collect over): a join + agg +
    order-by runs end-to-end on a 1-device mesh with exact rows."""
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.exec.dist_executor import DistEngine
    from presto_tpu.parallel import device_mesh
    from presto_tpu.types import BIGINT, VARCHAR

    customers = [(i, ["ASIA", "EMEA", "AMER"][i % 3]) for i in range(40)]
    orders = [(i, (i * 7) % 40, 100 + i) for i in range(500)]
    mem = MemoryConnector()
    mem.create("customer_t", [("custkey", BIGINT), ("region", VARCHAR)])
    mem.append_rows("customer_t", customers)
    mem.create("orders_t", [("okey", BIGINT), ("custkey", BIGINT),
                            ("amount", BIGINT)])
    mem.append_rows("orders_t", orders)
    sql = ("select c.region, count(*), sum(o.amount) "
           "from orders_t o join customer_t c on o.custkey = c.custkey "
           "group by c.region order by c.region")
    got = DistEngine(mem, device_mesh(1)).execute_sql(sql)

    db = sqlite3.connect(":memory:")
    db.execute("create table customer_t (custkey, region)")
    db.executemany("insert into customer_t values (?, ?)", customers)
    db.execute("create table orders_t (okey, custkey, amount)")
    db.executemany("insert into orders_t values (?, ?, ?)", orders)
    assert got == db.execute(sql).fetchall()
