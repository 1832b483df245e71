"""Streaming worker execution (round-3 VERDICT #3 acceptance).

1. A consumer observes >= 2 output-token advances while the producer task
   still reports RUNNING — pages flow per lifespan through the token/ack
   buffers, not in one burst at FINISH (reference: Driver.processFor +
   ClientBuffer incremental page delivery).
2. A worker executes a scan whose single-shot footprint is several times
   query_max_memory_per_node by subdividing lifespans — bounded memory on
   the HTTP path (reference: grouped execution bounding working sets).
3. Remote inputs are pulled in bounded chunks (X-Presto-Max-Size) — many
   small GETs instead of one giant drain.
"""

import json
import threading
import time
import urllib.request

import pytest

from presto_tpu.connectors import TpchConnector
from presto_tpu.exec import LocalEngine
from presto_tpu.exec.executor import MemoryLimitExceeded
from presto_tpu.exec.split_executor import SplitExecutor
from presto_tpu.protocol import structs as S
from presto_tpu.protocol.exchange_client import PageStream, decode_pages
from presto_tpu.server import TpuWorkerServer
from presto_tpu.types import DOUBLE
from tests.protocol_fixtures import (
    fragment, task_update_request, tpch_scan, var,
)

SF = 0.01


class SlowScanConnector:
    """Delegating connector that holds per-split table() fetches of one
    table — throttles the worker's lifespan loop so the test can
    observe mid-task state deterministically: for `delay_s`, or, with a
    `gate`, until gate(n) returns, n counting the table's splits in the
    order they are first asked for (lowering and scan both ask)."""

    def __init__(self, inner, slow_table: str, delay_s: float,
                 gate=None):
        self._inner = inner
        self._slow = slow_table
        self._delay = delay_s
        self._gate = gate
        self._ordinal = {}

    def table(self, name, part=None, num_parts=None, **kw):
        if name == self._slow and part is not None:
            if self._gate is None:
                time.sleep(self._delay)
            else:
                self._gate(self._ordinal.setdefault(
                    (part, num_parts), len(self._ordinal)))
        if part is None:
            return self._inner.table(name, **kw)
        return self._inner.table(name, part=part,
                                 num_parts=num_parts, **kw)

    def __getattr__(self, item):
        return getattr(self._inner, item)


def project_fragment(sf: float = SF) -> S.PlanFragment:
    """Pure row-preserving pipeline (streams without an aggregation):
    Project(extendedprice * discount) <- TableScan(lineitem)."""
    scan = tpch_scan("0", "lineitem", sf, [
        ("l_extendedprice", "l_extendedprice", "double"),
        ("l_discount", "l_discount", "double"),
    ])
    price = var("l_extendedprice", "double")
    disc = var("l_discount", "double")
    from tests.protocol_fixtures import call
    mul = call("MULTIPLY", "$operator$multiply", "double",
               [price, disc], ["double", "double"])
    proj = S.ProjectNode(
        id="1", source=scan,
        assignments=S.Assignments({"revenue<double>": mul}))
    return fragment("0", proj, [var("revenue", "double")], ["0"])


def _post(port, task_id, tur):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/task/{task_id}",
        data=tur.dumps().encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def _status(port, task_id):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/task/{task_id}/status",
        headers={"X-Presto-Max-Wait": "10ms"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def test_tokens_advance_while_running():
    conn = SlowScanConnector(TpchConnector(SF), "lineitem", 0.25)
    srv = TpuWorkerServer(conn).start()
    try:
        tur = task_update_request(project_fragment(), n_splits=6, sf=SF)
        _post(srv.port, "stream.0.0.0.0", tur)

        observations = []       # (state, end_token) while RUNNING
        stream = PageStream(
            f"http://127.0.0.1:{srv.port}/v1/task/stream.0.0.0.0",
            max_wait="50ms")
        frames = b""
        deadline = time.time() + 120
        while not stream.complete and time.time() < deadline:
            frames += stream.fetch()
            st = _status(srv.port, "stream.0.0.0.0")
            if st["state"] == "RUNNING":
                observations.append(stream.token)
        # the buffers complete a moment BEFORE the task says FINISHED
        # (task_manager: set_no_more_pages, spool commit, set_state)
        while (st := _status(srv.port, "stream.0.0.0.0"))["state"] \
                == "RUNNING" and time.time() < deadline:
            time.sleep(0.02)
        assert st["state"] == "FINISHED", st

        # >= 2 distinct token positions seen while the task was RUNNING:
        # output streamed during execution, not after.
        distinct_while_running = sorted(set(observations))
        assert len(distinct_while_running) >= 2, observations

        # and the streamed result is the full correct result
        pages = decode_pages(frames, [DOUBLE])
        got = sorted(r[0] for p in pages for r in p.to_pylist())
        exp = sorted(r[0] for r in LocalEngine(TpchConnector(SF))
                     .execute_sql("select l_extendedprice * l_discount "
                                  "from lineitem"))
        assert len(got) == len(exp)
        for g, e in zip(got, exp):
            assert abs(g - e) <= 1e-9 * max(abs(e), 1.0)
    finally:
        srv.stop()


def test_scan_beyond_memory_limit_finishes():
    conn = TpchConnector(SF)
    # find a limit the single-shot execution definitely exceeds
    from presto_tpu.protocol.translate import translate_fragment
    plan = translate_fragment(project_fragment())
    probe = SplitExecutor(conn)
    probe.set_splits({"lineitem": [(0, 1)]})
    probe.memory_limit_bytes = None
    probe.execute(plan)                      # measure footprint implicitly
    rows = conn.table("lineitem").num_rows
    # lineitem doubles: 2 in + 1 out per row, 8B each + nulls; a quarter
    # of that is comfortably exceeded by the single-shot plan
    limit = max((rows * 8 * 3) // 4, 1 << 16)

    single = SplitExecutor(conn)
    single.set_splits({"lineitem": [(0, 1)]})
    single.memory_limit_bytes = limit
    with pytest.raises(MemoryLimitExceeded):
        single.execute(plan)

    srv = TpuWorkerServer(conn).start()
    try:
        tur = task_update_request(
            project_fragment(), n_splits=1, sf=SF,
            session_properties={
                "query_max_memory_per_node": str(limit)})
        _post(srv.port, "mem.0.0.0.0", tur)
        state = "PLANNED"
        for _ in range(600):
            st = _status(srv.port, "mem.0.0.0.0")
            state = st["state"]
            if state in ("FINISHED", "FAILED"):
                break
            time.sleep(0.05)
        assert state == "FINISHED", st
        stream = PageStream(
            f"http://127.0.0.1:{srv.port}/v1/task/mem.0.0.0.0")
        pages = decode_pages(stream.drain(), [DOUBLE])
        n = sum(len(p.to_pylist()) for p in pages)
        assert n == rows
    finally:
        srv.stop()


def test_bounded_chunk_remote_pull():
    """X-Presto-Max-Size bounds each GET: pulling a multi-frame stream
    with a small cap takes several round trips, and the reassembled
    stream is identical."""
    conn = TpchConnector(SF)
    srv = TpuWorkerServer(conn).start()
    try:
        tur = task_update_request(project_fragment(), n_splits=4, sf=SF)
        _post(srv.port, "chunk.0.0.0.0", tur)
        for _ in range(600):
            if _status(srv.port, "chunk.0.0.0.0")["state"] == "FINISHED":
                break
            time.sleep(0.05)

        # unbounded drain for reference
        ref = PageStream(
            f"http://127.0.0.1:{srv.port}/v1/task/chunk.0.0.0.0").drain()
        # re-post an identical task to pull again bounded (tokens were
        # acknowledged/dropped by the reference drain)
        _post(srv.port, "chunk2.0.0.0.0", tur)
        for _ in range(600):
            if _status(srv.port, "chunk2.0.0.0.0")["state"] \
                    == "FINISHED":
                break
            time.sleep(0.05)
        bounded = PageStream(
            f"http://127.0.0.1:{srv.port}/v1/task/chunk2.0.0.0.0",
            max_size_bytes=1)           # 1 byte -> 1 frame per GET
        rounds = 0
        chunks = []
        while not bounded.complete:
            got = bounded.fetch()
            if got:
                rounds += 1
                chunks.append(got)
        bounded.close()
        assert rounds >= 4, rounds      # one frame per lifespan split
        assert b"".join(chunks) == ref
    finally:
        srv.stop()


def test_three_stage_pipeline_streams_through_middle_stage():
    """Non-leaf streaming (round-4 VERDICT #4 acceptance): stage-2 (a
    row-preserving fragment whose input is a RemoteSourceNode) emits
    output tokens while stage-1 is still RUNNING — pages flow through
    every stage of the section concurrently
    (SqlTaskExecution.java:509 semantics). No clock decides it: stage 1
    scans its n-th split only once this test has seen stage 2 put out
    what the split before it became, so stage 1 cannot finish first
    however slow the machine is, and a stage 2 that held its input
    back until stage 1 was done would leave the gate shut."""
    seen = threading.Condition()
    s2_token = [0]

    def gate(n):
        with seen:
            seen.wait_for(lambda: s2_token[0] >= n, timeout=60)

    conn = SlowScanConnector(TpchConnector(SF), "lineitem", 0.0, gate)
    srv = TpuWorkerServer(conn).start()
    try:
        # stage 1: leaf project fragment over the slow scan (streams
        # per lifespan)
        tur1 = task_update_request(project_fragment(), n_splits=6, sf=SF)
        _post(srv.port, "p3s1.0.0.0", tur1)

        # stage 2: Filter(revenue >= 0) <- RemoteSource(stage 1)
        rev = var("revenue", "double")
        remote = S.RemoteSourceNode(
            id="0", sourceFragmentIds=["0"], outputVariables=[rev])
        from tests.protocol_fixtures import call
        zero = call("GREATER_THAN_OR_EQUAL",
                    "$operator$greater_than_or_equal", "boolean",
                    [rev, rev], ["double", "double"])
        filt = S.FilterNode(id="1", source=remote, predicate=zero)
        frag2 = fragment("1", filt, [rev], ["0"])
        tur2 = task_update_request(frag2, n_splits=0, sf=SF)
        tur2.sources = [S.TaskSource(
            planNodeId="0",
            splits=[S.ScheduledSplit(
                sequenceId=0, planNodeId="0",
                split=S.Split(connectorId="$remote", connectorSplit={
                    "location":
                        f"http://127.0.0.1:{srv.port}/v1/task/p3s1.0.0.0",
                    "bufferId": "0"}))],
            noMoreSplits=True)]
        _post(srv.port, "p3s2.0.0.0", tur2)

        # stage 3 (this test): watch stage-2 tokens while stage-1 runs
        stream = PageStream(
            f"http://127.0.0.1:{srv.port}/v1/task/p3s2.0.0.0",
            max_wait="50ms")
        frames = b""
        s2_tokens_while_s1_running = set()
        deadline = time.time() + 180
        while not stream.complete and time.time() < deadline:
            frames += stream.fetch()
            s1 = _status(srv.port, "p3s1.0.0.0")
            if s1["state"] == "RUNNING" and stream.token > 0:
                s2_tokens_while_s1_running.add(stream.token)
            with seen:
                s2_token[0] = stream.token
                seen.notify_all()
        # the last frame is out before the task's state turns
        s2 = _status(srv.port, "p3s2.0.0.0")
        while s2["state"] == "RUNNING" and time.time() < deadline:
            s2 = _status(srv.port, "p3s2.0.0.0")
        assert s2["state"] == "FINISHED", s2
        assert len(s2_tokens_while_s1_running) >= 2, \
            s2_tokens_while_s1_running

        pages = decode_pages(frames, [DOUBLE])
        got = sorted(r[0] for p in pages for r in p.to_pylist())
        exp = sorted(r[0] for r in LocalEngine(TpchConnector(SF))
                     .execute_sql("select l_extendedprice * l_discount "
                                  "from lineitem"))
        assert len(got) == len(exp), (len(got), len(exp))
        for g, e in zip(got, exp):
            assert abs(g - e) <= 1e-9 * max(abs(e), 1.0)
    finally:
        srv.stop()
