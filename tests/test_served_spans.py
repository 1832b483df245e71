"""The served statement's fixed cost, each piece under a span of its own
(PERF.md section 3): the statement protocol's `statement_post` and
`statement_results` (back-dated markers built with the response), each
TaskUpdateRequest round trip (`task_post`, inside `schedule`), each
task's completion as its watcher learns of it (`task_notice`, from the
worker's `stateChangedAt`) and the ordered root merge's consumer loop
(`root_merge`, with what it spent blocked in `get`). One statement at
SF0.01 through POST /v1/statement on two workers: a grouped aggregation
whose two FINAL + Sort tasks the coordinator merges in order. CPU:
what is there and how it nests, never a time."""

import dataclasses
import json
import threading

import pytest

from presto_tpu.config import DEFAULT_OBS
from presto_tpu.connectors import TpchConnector
from presto_tpu.protocol import structs as S
from presto_tpu.protocol.transport import get_client
from presto_tpu.server import statement as statement_mod
from presto_tpu.server.cluster import TpuCluster, bounded_merge
from presto_tpu.server.statement import StatementServer
from presto_tpu.utils.tracing import TRACER, trace_scope

SQL = ("select l_returnflag, l_linestatus, count(*), sum(l_quantity) "
       "from lineitem group by l_returnflag, l_linestatus "
       "order by l_returnflag, l_linestatus")
NEW = {"statement_post", "statement_results", "task_post", "task_notice",
       "root_merge"}


@pytest.fixture(scope="module")
def served():
    cluster = TpuCluster(TpchConnector(0.01), n_workers=2)
    srv = StatementServer(cluster).start()
    try:
        yield srv, cluster
    finally:
        srv.stop()
        cluster.stop()


def _statement(base: str, sql: str):
    """POST, then follow nextUri: (query id, rows)."""
    client = get_client()
    payload = client.post(f"{base}/v1/statement", sql.encode(),
                          headers={"Content-Type": "text/plain"},
                          request_class="statement").json()
    qid, rows = payload["id"], []
    while True:
        assert "error" not in payload, payload
        rows.extend(payload.get("data", []))
        if not payload.get("nextUri"):
            return qid, rows
        payload = client.get_json(payload["nextUri"],
                                  request_class="statement")


@pytest.fixture(scope="module")
def traced(served):
    srv, _cluster = served
    qid, rows = _statement(srv.base, SQL)
    return qid, rows, TRACER.get(qid)


def _one(spans, name):
    got = [s for s in spans if s.name == name]
    assert len(got) == 1, (name, len(got))
    return got[0]


def test_the_protocol_spans_lie_around_the_statement(traced):
    _qid, rows, spans = traced
    assert len(rows) == 4
    post = _one(spans, "statement_post")
    stmt = _one(spans, "statement")
    wait = _one(spans, "admission_wait")
    assert post.start <= wait.start and post.end >= post.start
    assert post.attributes["bytes"] == len(SQL)
    assert post.attributes["journal"] is False     # no journal configured
    assert post.attributes["waited_ms"] >= 0
    results = [s for s in spans if s.name == "statement_results"]
    assert results
    last = max(results, key=lambda s: s.start)
    assert last.start >= stmt.end
    assert sum(s.attributes["rows"] for s in results) == len(rows)
    assert all(s.attributes["bytes"] > 0 and s.attributes["parked_ms"] >= 0
               for s in results)


def test_each_task_post_is_a_leaf_under_schedule(traced):
    _qid, _rows, spans = traced
    by_id = {s.span_id: s for s in spans}
    schedule = next(s for s in spans if s.name == "schedule"
                    and "tasks" in s.attributes)
    posts = [s for s in spans if s.name == "task_post"]
    assert len(posts) == schedule.attributes["tasks"] == 4   # 2 + 2
    for p in posts:
        assert by_id[p.parent_id] is schedule
        assert schedule.start <= p.start <= p.end <= schedule.end
        assert p.attributes["bytes"] > 0
        assert p.attributes["worker"].startswith("http://")
    runs = {s.attributes["task"] for s in spans if s.name == "task_run"}
    assert {p.attributes["task"] for p in posts} == runs


def test_each_task_notice_ends_after_its_task(traced):
    _qid, _rows, spans = traced
    by_id = {s.span_id: s for s in spans}
    runs = {s.attributes["task"]: s for s in spans if s.name == "task_run"}
    notices = [s for s in spans if s.name == "task_notice"]
    assert {n.attributes["task"] for n in notices} == set(runs)
    for n in notices:
        run = runs[n.attributes["task"]]
        assert n.end >= run.end and run.start <= n.start <= n.end
        assert n.attributes["waited_ms"] >= 0
        assert n.attributes["state"] == "FINISHED"
        assert n.attributes["polls"] >= 1
        assert by_id[n.parent_id].name == "await_tasks"


def test_the_ordered_root_merges_two_streams_once(traced):
    _qid, _rows, spans = traced
    merge = _one(spans, "root_merge")
    collect = next(s for s in spans if s.span_id == merge.parent_id)
    assert collect.name == "collect_root"
    assert merge.attributes["streams"] == 2
    assert merge.attributes["batches"] >= 1
    assert merge.attributes["blocked_ms"] >= 0
    assert merge.attributes["timeouts"] == 0     # each end came queued
    assert "waited_ms" not in merge.attributes   # a span, not a marker


def test_an_unsampled_statement_stores_none_of_them(served, monkeypatch):
    srv, _cluster = served
    monkeypatch.setattr(statement_mod, "DEFAULT_OBS", dataclasses.replace(
        DEFAULT_OBS, trace_sample_rate=0.0))
    qid, rows = _statement(srv.base, SQL)
    assert len(rows) == 4
    assert not {s.name for s in TRACER.get(qid)} & NEW
    assert srv.queries[qid].sampled is False


def test_a_status_says_when_its_state_changed_or_nothing():
    ref = {"state": "FINISHED", "version": 3}          # a C++ worker's
    st = S.TaskStatus.from_json(ref)
    assert st.stateChangedAt is None
    assert "stateChangedAt" not in S.TaskStatus.to_json(st)
    ours = json.loads(json.dumps(S.TaskStatus.to_json(
        dataclasses.replace(st, stateChangedAt=1.5e9))))
    assert ours["stateChangedAt"] == 1.5e9


def test_root_merge_ends_each_stream_on_its_marker():
    """A stream ends when its producer's end marker reaches the merging
    thread, not when a timed `get` runs out: no get runs out, and what is
    blocked is the real wait for the slow producers' rows."""
    slow = threading.Event()

    def source(vals):
        def batches():
            slow.wait(0.2)
            yield [(v,) for v in vals]
        return batches

    with trace_scope("merge-test"):
        rows, _high = bounded_merge([source([1, 3]), source([2, 4])],
                                    key=lambda r: r[0])
    assert rows == [(1,), (2,), (3,), (4,)]
    merge = _one(TRACER.get("merge-test"), "root_merge")
    assert merge.attributes["streams"] == 2
    assert merge.attributes["batches"] == 2
    assert merge.attributes["timeouts"] == 0
    assert merge.attributes["blocked_ms"] >= 150
