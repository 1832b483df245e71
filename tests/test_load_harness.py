"""Tier-1 gate for the admission front door, driven end-to-end through
the closed-loop load harness (testing/load.py):

  1. 200 concurrent statements from 3 tenants at weights 2:1:1, chaos
     off — ZERO dropped queries (every statement completes or is
     cleanly rejected/shed), WFQ dispatch ratio within 30% of the
     configured weights in the saturated window, and no unbounded
     thread growth (execution rides the fixed dispatch pool; the old
     thread-per-query pattern is gone).
  2. Load shedding with forced-low thresholds: the server answers
     429/503 + Retry-After, the dbapi client retries on the server's
     schedule and completes, and the episode is visible in
     presto_tpu_admission_* metrics and GET /v1/status.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from presto_tpu.admission import (ResourceGroup, ResourceGroupManager,
                                  Selector)
from presto_tpu.config import AdmissionConfig
from presto_tpu.server.statement import StatementServer
from presto_tpu.testing import LoadHarness

TENANTS = {"alpha": 2, "beta": 1, "gamma": 1}


class StubEngine:
    """Minimal engine: a fixed per-statement service time makes
    saturation deterministic without JAX in the loop."""

    def __init__(self, service_s=0.03, gate=None):
        self.service_s = service_s
        self.gate = gate

    def execute_sql(self, sql):
        if self.gate is not None:
            self.gate.wait(30)
        elif self.service_s:
            time.sleep(self.service_s)
        return [(1,)]

    def plan_sql(self, sql):
        raise ValueError("stub has no planner")


def _tenant_tree(max_queued=300):
    leaves = [ResourceGroup(n, hard_concurrency=4,
                            max_queued=max_queued,
                            scheduling_weight=w)
              for n, w in TENANTS.items()]
    root = ResourceGroup("front", hard_concurrency=4, max_queued=0,
                         children=leaves)
    return ResourceGroupManager(
        [root],
        [Selector(n, user_regex=n) for n in TENANTS]
        + [Selector("alpha")])


# ===================================================================
# 1. the saturation gate
# ===================================================================

def test_front_door_200_statements_zero_dropped_wfq_bounded():
    mgr = _tenant_tree()
    srv = StatementServer(
        StubEngine(service_s=0.03),
        resource_groups=mgr,
        admission=AdmissionConfig(max_dispatch_threads=4))
    srv.start()
    try:
        harness = LoadHarness(srv.base, TENANTS, clients=200,
                              statements=200, seed=7, timeout_s=120.0)
        report = harness.run(dispatcher=srv.dispatcher, groups=mgr)

        # the zero-dropped-query invariant + a balanced ledger
        report.assert_zero_dropped()
        assert report.completed == 200      # nothing even sheds here

        # WFQ: saturated-window dispatch shares within 30% of 2:1:1
        report.assert_wfq_ratio(tolerance=0.30)

        # bounded execution: the fixed dispatch pool ran everything —
        # the old thread-per-query pattern would leave query-* threads
        assert not [t.name for t in threading.enumerate()
                    if "-query-" in t.name]
        pool = [t.name for t in threading.enumerate()
                if "-dispatch-" in t.name]
        assert len(pool) == 4
        assert srv.dispatcher.snapshot()["pool_size"] == 4

        # queue-wait percentiles made it into the report
        assert len(report.queue_wait_s) == 200
        assert report.latency()["queue_wait_p99_s"] > 0.0
    finally:
        srv.stop()


def test_harness_classifies_clean_rejection_not_drop():
    """max_queued=1 on every tenant: overflow must land in the
    `rejected` column (clean QUERY_QUEUE_FULL), never in `dropped`."""
    mgr = _tenant_tree(max_queued=1)
    srv = StatementServer(
        StubEngine(service_s=0.05),
        resource_groups=mgr,
        admission=AdmissionConfig(max_dispatch_threads=4))
    srv.start()
    try:
        harness = LoadHarness(srv.base, TENANTS, clients=40,
                              statements=40, seed=3, timeout_s=60.0)
        report = harness.run(dispatcher=srv.dispatcher, groups=mgr)
        report.assert_zero_dropped()        # rejected != dropped
        assert report.rejected > 0
        assert report.completed + report.rejected == 40
    finally:
        srv.stop()


# ===================================================================
# 2. the long-poll storm: 1000 clients on the event-loop front door
# ===================================================================

def _storm_tree():
    leaves = [ResourceGroup(n, hard_concurrency=32, max_queued=1500,
                            scheduling_weight=w)
              for n, w in TENANTS.items()]
    root = ResourceGroup("front", hard_concurrency=32, max_queued=0,
                         children=leaves)
    return ResourceGroupManager(
        [root],
        [Selector(n, user_regex=n) for n in TENANTS]
        + [Selector("alpha")])


def test_long_poll_storm_1000_clients_flat_server_threads():
    """Scale the closed-loop harness 200 -> 1000 concurrent clients.
    Most clients spend their life parked in a nextUri long-poll; with
    the event-loop front door those parks live on the loop, not on
    threads, so the server-side thread population must stay flat while
    the client population grows 5x — and nothing may drop.  Keep-alive
    reuse on the pooled client transport must be visible."""
    from presto_tpu.net import M_KEEPALIVE_REUSE

    srv = StatementServer(
        StubEngine(service_s=0.005),
        resource_groups=_storm_tree(),
        admission=AdmissionConfig(max_dispatch_threads=8))
    srv.start()
    try:
        base = LoadHarness(srv.base, TENANTS, clients=200,
                           statements=200, seed=11,
                           timeout_s=120.0).run()
        base.assert_zero_dropped()
        assert base.completed == 200

        reuse0 = M_KEEPALIVE_REUSE.value(role="client-pool")
        storm = LoadHarness(srv.base, TENANTS, clients=1000,
                            statements=1000, seed=13,
                            timeout_s=240.0).run()
        storm.assert_zero_dropped()
        assert storm.completed == 1000

        # the tentpole claim: 5x the clients, flat server threads.
        # Loop + fixed executor + fixed dispatch pool — parked polls
        # cost a loop task, never a thread (a thread-per-connection
        # server would show ~+800 here).
        assert (storm.peak_server_threads
                <= base.peak_server_threads + 8), (
            f"server thread population grew with client count: "
            f"{base.peak_server_threads} @200 -> "
            f"{storm.peak_server_threads} @1000")

        # closed-loop e2e p99 grows with the backlog (5x statements),
        # so allow linear scaling with headroom; thread-per-connection
        # collapse is superlinear and blows through this. The ratio
        # holds only between runs under the same load, and five other
        # test workers change the machine's between two phases: the
        # 200-client run is taken again after the storm and the slower
        # of the two is the base
        again = LoadHarness(srv.base, TENANTS, clients=200,
                            statements=200, seed=17,
                            timeout_s=120.0).run()
        again.assert_zero_dropped()
        base_p99 = max(base.latency()["e2e_p99_s"],
                       again.latency()["e2e_p99_s"], 0.2)
        storm_p99 = storm.latency()["e2e_p99_s"]
        assert storm_p99 <= 10 * base_p99, (
            f"e2e p99 collapsed under the storm: {storm_p99:.2f}s vs "
            f"{base_p99:.2f}s at 200 clients")

        # pooled keep-alive transport actually reused sockets
        assert M_KEEPALIVE_REUSE.value(role="client-pool") > reuse0

        # the serving tier reports its loop stats on /v1/status
        with urllib.request.urlopen(f"{srv.base}/v1/status",
                                    timeout=10) as resp:
            status = json.loads(resp.read())
        net = status["net"]
        assert net["impl"] == "aio"
        assert net["requestsServed"] > 1000
        assert net["asyncServed"] > 0
    finally:
        srv.stop()


# ===================================================================
# 3. the shedding episode
# ===================================================================

def _post(base, sql, user="alpha"):
    req = urllib.request.Request(
        f"{base}/v1/statement", data=sql.encode(), method="POST",
        headers={"X-Presto-User": user})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, dict(resp.headers), resp.read()


def test_shedding_returns_503_retry_after_and_dbapi_recovers():
    from presto_tpu.client.dbapi import connect
    from presto_tpu.obs.metrics import render_prometheus
    from presto_tpu.protocol.transport import (_M_RETRY_AFTER, _host_of,
                                               get_client)

    gate = threading.Event()
    mgr = _tenant_tree()
    srv = StatementServer(
        StubEngine(gate=gate),
        resource_groups=mgr,
        admission=AdmissionConfig(max_dispatch_threads=2,
                                  shed_max_queued=2,
                                  retry_after_s=0.5))
    srv.start()
    try:
        host = _host_of(srv.base)
        honored_before = _M_RETRY_AFTER.value(host=host)
        # hard-reset this host's breaker state from earlier tests
        get_client().breaker(srv.base).record_success()

        # saturate: 6 statements block on the gate — 4 hold admission
        # slots (2 running on the pool, 2 awaiting a pool thread), the
        # last 2 queue in the group -> depth hits the shed threshold
        for i in range(6):
            _post(srv.base, f"select {i}")
        deadline = time.monotonic() + 5
        while (mgr.total_queued() < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert mgr.total_queued() >= 2

        # the door now sheds: 503 + Retry-After + a well-formed body
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.base, "select 99")
        assert ei.value.code == 503
        assert ei.value.headers["Retry-After"] == "0.5"
        body = json.loads(ei.value.read())
        assert body["error"]["errorName"] == "SERVER_OVERLOADED"
        assert body["error"]["errorType"] == "INSUFFICIENT_RESOURCES"
        assert body["error"]["retryAfterSeconds"] == 0.5

        # the dbapi client sees the shed, sleeps the advised interval,
        # retries after the episode clears, and completes
        threading.Timer(0.25, gate.set).start()
        with connect(srv.base, timeout_s=30, user="beta") as conn:
            cur = conn.cursor()
            cur.execute("select 'recovered'")
            assert cur.fetchall() == [[1]] or cur.rowcount == 1
        assert _M_RETRY_AFTER.value(host=host) >= honored_before + 1

        # the episode is on the books: shed counters + /v1/status
        assert srv.dispatcher.shedder.shed_counts["queue_depth"] >= 2
        text = render_prometheus()
        assert "presto_tpu_admission_shed_total" in text
        with urllib.request.urlopen(f"{srv.base}/v1/status",
                                    timeout=10) as resp:
            status = json.loads(resp.read())
        assert status["admission"]["shed"]["queue_depth"] >= 2
        assert status["admission"]["thresholds"]["max_queued"] == 2
        rows = status["resourceGroups"]
        assert "front.alpha" in rows and "front.beta" in rows
        assert rows["front.alpha"]["weight"] == 2
        assert rows["front.alpha"]["admitted"] >= 1
    finally:
        gate.set()
        srv.stop()
