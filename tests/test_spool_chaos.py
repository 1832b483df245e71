"""Chaos suite for stage-level recoverable execution
(`retry_policy=TASK`): kill one worker mid-query across a seed matrix
and require ORACLE-CORRECT rows — not merely a clean failure.

This is the contract the spool subsystem exists for (Presto@Meta
VLDB'23 §3 fault-tolerant execution): with task output spooled and
committed atomically, a worker death costs only its uncommitted tasks.
An execution probe on the REAL task entry point
(`TpuTaskManager._run_inner`) proves the stronger claim behind the
rows: committed (absorbed-from-spool) tasks are never re-executed, and
every attempt>0 execution corresponds to a recorded recovery re-plan.
Results are checked against an independent sqlite oracle, not a
cluster baseline — a recovery bug that corrupts rows deterministically
would poison a cluster-produced baseline too.

The final test is the stray-directory guard for the chaos family, as
far as this test process ran it (tests/conftest.py gives every process
a temp root of its own; in one process this module follows
tests/test_chaos.py, so both matrices have run): no new
`presto_tpu_spill_*` / `presto_tpu_spool_*` / `presto_tpu_shuffle_*`
entries may survive in the temp dir."""

import math
import os
import sqlite3
import tempfile
import time

import pytest

from presto_tpu.config import TransportConfig
from presto_tpu.connectors import TpchConnector
from presto_tpu.protocol import transport as _transport
from presto_tpu.protocol.structs import TaskId
from presto_tpu.server.cluster import ClusterQueryError, TpuCluster
from presto_tpu.server.task_manager import TpuTaskManager
from presto_tpu.spool.store import spool_counters
from presto_tpu.testing import FaultInjector, FaultSpec

SF = 0.01

#: snapshot BEFORE any test in the session runs (pytest imports all
#: modules at collection time) — the guard at the bottom diffs against
#: this after both chaos matrices are done
_TMP_PREFIXES = ("presto_tpu_spill_", "presto_tpu_spool_",
                 "presto_tpu_shuffle_")
_PREEXISTING_TMP = {n for n in os.listdir(tempfile.gettempdir())
                    if n.startswith(_TMP_PREFIXES)}

#: same exchange-shape coverage as tests/test_chaos.py: single gather;
#: hash-partitioned partial/final aggregation; join + grouped agg
QUERIES = (
    "select count(*) from lineitem",
    "select l_returnflag, l_linestatus, count(*), sum(l_quantity) "
    "from lineitem group by l_returnflag, l_linestatus "
    "order by l_returnflag, l_linestatus",
    "select r_name, count(*) from nation, region "
    "where n_regionkey = r_regionkey group by r_name order by r_name",
)

CHAOS_TRANSPORT = TransportConfig(
    retry_base_backoff_s=0.01, retry_max_backoff_s=0.2,
    retry_budget_s=5.0, breaker_failure_threshold=3,
    breaker_cooldown_s=0.3)

DEADLINE_S = 120.0

#: request count to the victim before it "dies" — varies per seed so
#: the kill lands at different protocol phases (task create, status
#: poll, page pull, between queries)
KILL_AFTER = (5, 12, 20, 30, 45)


@pytest.fixture(scope="module")
def cluster():
    c = TpuCluster(
        TpchConnector(SF), n_workers=3,
        session_properties={"query_max_execution_time": str(DEADLINE_S),
                            "retry_policy": "TASK"},
        transport_config=CHAOS_TRANSPORT)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def oracle():
    """Independent sqlite oracle over the same connector data."""
    conn = TpchConnector(SF)
    db = sqlite3.connect(":memory:")
    for name in ("lineitem", "nation", "region"):
        page = conn.table(name).page()
        cols = list(page.names)
        db.execute(f"create table {name} ({', '.join(cols)})")
        db.executemany(
            f"insert into {name} values "
            f"({', '.join('?' * len(cols))})", page.to_pylist())
    db.commit()
    want = {sql: db.execute(sql).fetchall() for sql in QUERIES}
    db.close()
    return want


def _assert_rows_match(got, want, ctx=""):
    assert len(got) == len(want), \
        f"{ctx}: {len(got)} rows, oracle has {len(want)}"
    for g, w in zip(sorted(got), sorted(want)):
        assert len(g) == len(w), f"{ctx}: row arity {g} vs {w}"
        for gc, wc in zip(g, w):
            if isinstance(wc, float) or isinstance(gc, float):
                assert math.isclose(gc, wc, rel_tol=1e-6, abs_tol=1e-9), \
                    f"{ctx}: {g} vs oracle {w}"
            else:
                assert gc == wc, f"{ctx}: {g} vs oracle {w}"


@pytest.fixture()
def probe(monkeypatch):
    """Record every REAL task execution (stage, task-index, attempt)
    through the worker's actual entry point."""
    executed = []
    orig = TpuTaskManager._run_inner

    def spy(self, task):
        try:
            tid = TaskId.parse(task.task_id)
            executed.append((tid.stage_id, tid.task_index, tid.attempt))
        except ValueError:
            pass
        return orig(self, task)

    monkeypatch.setattr(TpuTaskManager, "_run_inner", spy)
    return executed


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
def test_task_retry_kill_worker_matrix(cluster, oracle, probe, seed):
    hosts = sorted(u.split("://", 1)[1] for u in cluster.all_worker_uris)
    victim = hosts[seed % len(hosts)]
    shared = _transport.get_client()

    def run_queries(kill_after):
        # ONE shared injector on both transports: the coordinator's
        # client AND the process-global client the workers pull pages
        # through — the victim must look dead to every node, exactly
        # like a real kill
        inj = FaultInjector(seed=seed,
                            spec=FaultSpec(
                                kill_after={victim: kill_after}),
                            only_hosts={victim})
        cluster.http.fault_injector = inj
        shared.fault_injector = inj
        try:
            for sql in QUERIES:
                del probe[:]
                start = time.monotonic()
                # under retry_policy=TASK a single worker death with
                # two survivors must NOT fail the query — correct rows
                # required
                got = cluster.execute_sql(sql)
                assert time.monotonic() - start < DEADLINE_S + 60, \
                    f"query exceeded deadline under seed {seed}: {sql!r}"
                _assert_rows_match(got, oracle[sql],
                                   ctx=f"seed {seed} {sql!r}")
                # execution probe: completed (spool-absorbed) tasks
                # never re-execute; every attempt>0 execution is a
                # recorded recovery re-plan of that exact work unit
                events = list(getattr(cluster, "last_recovery_events",
                                      []))
                retasked = {(f, t) for kind, f, t in events
                            if kind == "retask"}
                absorbed = {(f, t) for kind, f, t in events
                            if kind == "spool"}
                rerun = {(f, t) for f, t, att in probe if att > 0}
                assert rerun <= retasked, \
                    (f"seed {seed}: tasks {sorted(rerun - retasked)} "
                     "re-executed without a recorded recovery")
                assert not (absorbed & rerun), \
                    (f"seed {seed}: spool-absorbed (completed) tasks "
                     f"{sorted(absorbed & rerun)} were re-executed")
                # end-of-query retention: the spool base holds nothing
                assert os.listdir(cluster.spool.base_dir) == [], \
                    f"seed {seed}: spool not GC'd after {sql!r}"
        finally:
            cluster.http.fault_injector = None
            shared.fault_injector = None
            inj.revive(victim)
            _stabilize(cluster)

    # The kill must engage recovery at least once per seed. The kill
    # ordinal is request-count based while query progress is
    # wall-clock, so on a fast run the victim's fatal request can land
    # in the tail of a query or in the idle gap between queries — the
    # next query then simply plans around the already-dead worker:
    # correct rows, zero recoveries, nothing exercised. That timing is
    # legal, so re-arm the kill at a shifted protocol phase until it
    # lands mid-flight (every productive landing spot increments the
    # recovery counter: absorb or retask).
    before = spool_counters()["recoveries"]
    # (six phases since PR 32: workers keep their programs, a repeated
    # query takes half a second, and three neighbouring ordinals could
    # all fall into one gap)
    for attempt in range(6):
        run_queries(max(2, KILL_AFTER[seed] - 3 * attempt))
        if spool_counters()["recoveries"] - before >= 1:
            break
    assert spool_counters()["recoveries"] - before >= 1, \
        f"seed {seed}: worker kill never triggered recovery"


def test_failed_middle_task_costs_no_rows(cluster, oracle, monkeypatch):
    """What seed 4 of the matrix lost, forced at its protocol phase
    instead of waited for: a middle-stage task on a LIVE worker fails
    (in the matrix: its pull from the killed producer ran out before
    the replacement attempt had committed) while the final stage's
    tasks are already pulling from it, and the coordinator's recovery
    round comes only after they have had their answer. The failed
    attempt used to answer `complete` with no frames, so the final
    stage finished without that task's share of `nation` (18 of 25
    rows) and recovery, which re-plans FAILED tasks only, never looked
    at it again. No kill and no clock: the failure is raised in the
    task, and the first recovery round is held until every final-stage
    task of attempt 0 has either finished or failed."""
    sql = QUERIES[2]
    failed = []
    orig_pull = TpuTaskManager._pull_remote_inputs

    def pull(self, task, plan, skip=None):
        tid = TaskId.parse(task.task_id)
        if (tid.stage_id, tid.task_index, tid.attempt) == (2, 0, 0):
            failed.append(task.task_id)
            raise OSError("producer killed before its spool committed")
        return orig_pull(self, task, plan, skip=skip)

    def final_stage_pending():
        return [t.task_id for w in cluster.workers
                for t in list(w.task_manager.tasks.values())
                if TaskId.parse(t.task_id).stage_id == 0
                and t.state in ("PLANNED", "RUNNING")]

    orig_recover = TpuCluster._recover_spooled
    held = []

    def recover(self, qid, stages, by_id):
        deadline = time.monotonic() + DEADLINE_S
        while not held and final_stage_pending() \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        held.append(qid)
        return orig_recover(self, qid, stages, by_id)

    monkeypatch.setattr(TpuTaskManager, "_pull_remote_inputs", pull)
    monkeypatch.setattr(TpuCluster, "_recover_spooled", recover)
    got = cluster.execute_sql(sql)
    assert len(failed) == 1, "the middle-stage task never ran"
    _assert_rows_match(got, oracle[sql], ctx="failed middle task")
    assert ("retask", 2, 0) in cluster.last_recovery_events
    assert os.listdir(cluster.spool.base_dir) == []


def test_retry_policy_none_same_fault_fails_cleanly():
    """Control group: the SAME kill without retry_policy=TASK must
    either produce exact rows (whole-query retry on survivors) or raise
    a clean ClusterQueryError — never a hang, never wrong rows."""
    c = TpuCluster(TpchConnector(SF), n_workers=3,
                   session_properties={"query_max_execution_time":
                                       str(DEADLINE_S)},
                   transport_config=CHAOS_TRANSPORT)
    try:
        sql = QUERIES[1]
        want = c.execute_sql(sql)
        hosts = sorted(u.split("://", 1)[1] for u in c.all_worker_uris)
        victim = hosts[0]
        inj = FaultInjector(seed=0,
                            spec=FaultSpec(kill_after={victim: 5}),
                            only_hosts={victim})
        shared = _transport.get_client()
        c.http.fault_injector = inj
        shared.fault_injector = inj
        start = time.monotonic()
        try:
            got = c.execute_sql(sql)
        except ClusterQueryError:
            got = None              # clean failure is allowed here
        assert time.monotonic() - start < DEADLINE_S + 60
        if got is not None:
            assert got == want
        # no spool store exists under retry_policy=NONE
        assert c.spool is None
    finally:
        c.http.fault_injector = None
        shared.fault_injector = None
        c.stop()


def test_no_stray_spill_or_spool_dirs_after_chaos(cluster):
    """Runs after the chaos matrices of this process (tests/test_chaos.py
    sorts before this module; this test is last in it): every spill /
    spool / shuffle temp entry this process created must be gone — the
    exception-safe FileSpiller teardown and the spool GC are what keep
    a long-lived cluster's disk from filling. The module cluster's own
    spool base is still alive here (fixture teardown comes later), so
    it is exempt by name — but must already be GC'd empty."""
    own = os.path.basename(cluster.spool.base_dir)
    assert os.listdir(cluster.spool.base_dir) == []
    leaked = sorted(
        n for n in os.listdir(tempfile.gettempdir())
        if n.startswith(_TMP_PREFIXES) and n not in _PREEXISTING_TMP
        and n != own)
    assert not leaked, f"temp directories leaked by the suite: {leaked}"
