"""Spool store — commit protocol, integrity validation, retention, and
the serve-from-spool read paths (HTTP fallback on the worker, PageStream
fallback on the consumer).

Reference roles: the exchange manager behind Presto's TASK retry policy
(Presto@Meta VLDB'23 §3 fault-tolerant execution / Trino Project
Tardigrade): spooled task output must be atomic to commit, checksummed
to read, addressable by any attempt, and garbage-collected at query
end."""

import json
import os
import struct
import urllib.request

import pytest

from presto_tpu.config import SpoolConfig, TransportConfig
from presto_tpu.connectors import TpchConnector
from presto_tpu.exec import LocalEngine
from presto_tpu.protocol.exchange_client import PageStream, decode_pages
from presto_tpu.protocol.structs import TaskId
from presto_tpu.protocol.transport import HttpClient
from presto_tpu.spool import (
    FrameFile, SpoolIntegrityError, SpoolStore, frame_slices,
)
from presto_tpu.types import DOUBLE

SF = 0.01

FAST = TransportConfig(retry_base_backoff_s=0.001,
                       retry_max_backoff_s=0.01,
                       retry_budget_s=2.0,
                       probe_timeout_s=0.5, control_timeout_s=2.0,
                       page_fetch_timeout_s=2.0, page_fetch_attempts=2)


def _frame(payload: bytes) -> bytes:
    """Syntactically complete SerializedPage frame (framing walk only)."""
    return struct.pack("<ibiiq", 1, 0, len(payload), len(payload),
                       0) + payload


# ---------------------------------------------------------------- TaskId

def test_task_id_roundtrip():
    tid = TaskId.parse("20260805_q7.2.0.5.3")
    assert (tid.query_id, tid.stage_id, tid.task_index, tid.attempt) \
        == ("20260805_q7", 2, 5, 3)
    assert str(tid) == "20260805_q7.2.0.5.3"
    assert str(tid.with_attempt(4)) == "20260805_q7.2.0.5.4"
    # query ids may themselves contain dots: rsplit keeps them intact
    assert TaskId.parse("a.b.1.0.2.0").query_id == "a.b"


@pytest.mark.parametrize("bad", ["", "justaquery", "q.1.0.2",
                                 "q.x.0.2.0", ".1.0.2.0", "q.1.0.2.x"])
def test_task_id_malformed_raises(bad):
    with pytest.raises(ValueError):
        TaskId.parse(bad)


# -------------------------------------------------------------- FrameFile

def test_frame_file_append_read_range(tmp_path):
    f = FrameFile(str(tmp_path / "part.bin"))
    frames = [_frame(bytes([i]) * (10 + i)) for i in range(5)]
    for fr in frames:
        assert f.append(fr)
    assert f.frame_count == 5
    # replayable from any token, never skipping or duplicating
    got, nxt = f.read_range(0, 10 ** 9)
    assert got == frames and nxt == 5
    got, nxt = f.read_range(2, 10 ** 9)
    assert got == frames[2:] and nxt == 5
    # size cap still yields at least one frame
    got, nxt = f.read_range(0, 1)
    assert got == [frames[0]] and nxt == 1
    # the on-disk bytes rebuild the same index
    data = (tmp_path / "part.bin").read_bytes()
    assert [ln for _, ln in frame_slices(data)] == \
        [len(fr) for fr in frames]
    f.close(unlink=False)
    assert not f.append(frames[0])      # closed file refuses appends
    assert os.path.exists(str(tmp_path / "part.bin"))


# -------------------------------------------------- commit protocol

def _store(tmp_path, name="base"):
    base = str(tmp_path / name)
    return SpoolStore(SpoolConfig(enabled=True, base_dir=base,
                                  sweep_on_start=False))


def _commit_task(store, task_id, frames, buffer_id="0",
                 instance="inst-1"):
    w = store.writer(task_id)
    part = w.part(buffer_id)
    for fr in frames:
        part.append(fr)
    w.commit(instance)
    return w


def test_commit_is_atomic_and_visible(tmp_path):
    store = _store(tmp_path)
    frames = [_frame(b"abc"), _frame(b"defg")]
    w = store.writer("q1.0.0.0.0")
    part = w.part("0")
    for fr in frames:
        part.append(fr)
    # nothing committed yet: the tmp dir is invisible to every reader
    assert store.find_committed("q1", 0, 0) is None
    qdir = os.path.join(store.base_dir, "q1")
    assert all(n.startswith(".tmp-") for n in os.listdir(qdir))
    w.commit("inst-7")
    committed = store.find_committed("q1", 0, 0)
    assert committed is not None
    assert committed.instance_id == "inst-7"
    assert committed.frame_count("0") == 2
    assert committed.frames("0") == frames
    assert committed.frames("0", start=1) == frames[1:]
    # no tmp residue after the rename
    assert not [n for n in os.listdir(qdir) if n.startswith(".tmp-")]
    store.close()


def test_discarded_spool_never_visible(tmp_path):
    store = _store(tmp_path)
    w = store.writer("q1.0.0.0.0")
    w.part("0").append(_frame(b"abc"))
    w.discard()
    assert store.find_committed("q1", 0, 0) is None
    assert os.listdir(os.path.join(store.base_dir, "q1")) == []


def test_corrupt_part_raises_integrity_error(tmp_path):
    store = _store(tmp_path)
    _commit_task(store, "q1.0.0.0.0", [_frame(b"abcdef")])
    committed = store.find_committed("q1", 0, 0)
    part = os.path.join(committed.path, "part_0.bin")
    data = bytearray(open(part, "rb").read())
    data[-1] ^= 0xFF                      # flip a payload byte
    with open(part, "wb") as f:
        f.write(bytes(data))
    with pytest.raises(SpoolIntegrityError):
        store.find_committed("q1", 0, 0).frames("0")


def test_truncated_part_raises_integrity_error(tmp_path):
    store = _store(tmp_path)
    _commit_task(store, "q1.0.0.0.0", [_frame(b"abc"), _frame(b"def")])
    committed = store.find_committed("q1", 0, 0)
    part = os.path.join(committed.path, "part_0.bin")
    data = open(part, "rb").read()
    with open(part, "wb") as f:
        f.write(data[:len(data) // 2])    # cut mid-frame
    with pytest.raises(SpoolIntegrityError):
        store.find_committed("q1", 0, 0).frames("0")


def test_manifest_frame_count_mismatch_raises(tmp_path):
    store = _store(tmp_path)
    _commit_task(store, "q1.0.0.0.0", [_frame(b"abc"), _frame(b"def")])
    committed = store.find_committed("q1", 0, 0)
    mpath = os.path.join(committed.path, "manifest.json")
    doc = json.loads(open(mpath, "rb").read())
    doc["buffers"]["0"]["frames"] = 3     # claims a frame that is not
    part = os.path.join(committed.path, "part_0.bin")
    import zlib
    doc["buffers"]["0"]["crc32"] = zlib.crc32(open(part, "rb").read())
    with open(mpath, "w") as f:
        json.dump(doc, f)
    with pytest.raises(SpoolIntegrityError):
        store.find_committed("q1", 0, 0).frames("0")


def test_find_committed_prefers_highest_attempt(tmp_path):
    store = _store(tmp_path)
    _commit_task(store, "q1.0.0.0.0", [_frame(b"old")])
    _commit_task(store, "q1.0.0.0.2", [_frame(b"new"), _frame(b"er")])
    committed = store.find_committed("q1", 0, 0)
    assert committed.frame_count("0") == 2
    # lookup by ANY attempt's id lands on the newest committed one
    by_task = store.find_committed_for_task("q1.0.0.0.0")
    assert by_task.task_id == "q1.0.0.0.2"
    by_loc = store.find_committed_for_location(
        "http://127.0.0.1:9/v1/task/q1.0.0.0.1")
    assert by_loc.task_id == "q1.0.0.0.2"
    # unrelated tasks unaffected
    assert store.find_committed("q1", 0, 1) is None
    assert store.find_committed_for_task("not-a-task-id") is None


def test_duplicate_commit_keeps_existing(tmp_path):
    store = _store(tmp_path)
    _commit_task(store, "q1.0.0.0.0", [_frame(b"first")])
    # at-least-once task updates: a second writer for the SAME id
    # commits into an already-published name and must not corrupt it
    _commit_task(store, "q1.0.0.0.0", [_frame(b"second-attempt")])
    committed = store.find_committed("q1", 0, 0)
    assert committed.frame_count("0") == 1
    committed.frames("0")                # still integrity-clean


def test_gc_query_removes_whole_tree(tmp_path):
    store = _store(tmp_path)
    _commit_task(store, "q1.0.0.0.0", [_frame(b"abc")])
    _commit_task(store, "q1.1.0.2.0", [_frame(b"def")])
    _commit_task(store, "q2.0.0.0.0", [_frame(b"ghi")])
    assert store.gc_query("q1")
    assert not os.path.isdir(os.path.join(store.base_dir, "q1"))
    assert store.find_committed("q2", 0, 0) is not None
    assert not store.gc_query("q1")      # idempotent


def test_orphan_sweep_on_restart(tmp_path):
    base = str(tmp_path / "shared")
    s1 = SpoolStore(SpoolConfig(enabled=True, base_dir=base,
                                sweep_on_start=False))
    _commit_task(s1, "dead_query.0.0.0.0", [_frame(b"abc")])
    # a TTL larger than the tree's age spares it (live queries on a
    # shared base survive a node joining)
    SpoolStore(SpoolConfig(enabled=True, base_dir=base,
                           sweep_on_start=True, orphan_ttl_s=3600.0))
    assert s1.find_committed("dead_query", 0, 0) is not None
    # a process restarting over its own base sweeps any age
    SpoolStore(SpoolConfig(enabled=True, base_dir=base,
                           sweep_on_start=True, orphan_ttl_s=0.0))
    assert s1.find_committed("dead_query", 0, 0) is None
    assert os.listdir(base) == []


# ------------------------------------------- PageStream spool fallback

def test_pagestream_falls_back_to_spool_no_skip_no_dup(tmp_path):
    store = _store(tmp_path)
    frames = [_frame(bytes([i]) * 20) for i in range(6)]
    _commit_task(store, "q1.0.0.0.1", frames)
    # nothing listens on this port: every HTTP fetch dies fast, and the
    # stream must switch to the committed spool at its CURRENT token
    stream = PageStream("http://127.0.0.1:9/v1/task/q1.0.0.0.0",
                        client=HttpClient(FAST), spool=store)
    stream.token = 2          # frames 0-1 were already acked over HTTP
    out = b""
    while not stream.complete:
        out += stream.fetch()
    assert out == b"".join(frames[2:])   # no dup of 0-1, no skip of 2-5
    assert stream.token == 6
    stream.close()                        # no live buffer: must not raise


def test_pagestream_without_spool_still_raises(tmp_path):
    stream = PageStream("http://127.0.0.1:9/v1/task/q1.0.0.0.0",
                        client=HttpClient(FAST), spool=None)
    with pytest.raises(OSError):
        stream.fetch()


# ------------------------------------- worker HTTP serve-from-spool

def test_worker_serves_results_from_spool_after_task_delete(tmp_path):
    from presto_tpu.server import TpuWorkerServer
    from tests.protocol_fixtures import q6_fragment, task_update_request

    scfg = SpoolConfig(enabled=True, base_dir=str(tmp_path / "spool"),
                       sweep_on_start=False)
    srv = TpuWorkerServer(TpchConnector(SF), spool_config=scfg).start()
    try:
        task_id = "q_fixture.0.0.0.0"
        tur = task_update_request(
            q6_fragment(SF), n_splits=2, sf=SF,
            session_properties={"retry_policy": "TASK"})
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/task/{task_id}",
            data=tur.dumps().encode(), method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
        # wait for FINISHED, then DELETE the task — its live buffers die
        state = "PLANNED"
        for _ in range(600):
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/task/{task_id}/status",
                headers={"X-Presto-Current-State": state,
                         "X-Presto-Max-Wait": "1s"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                state = json.loads(resp.read())["state"]
            if state in ("FINISHED", "FAILED", "ABORTED"):
                break
        assert state == "FINISHED"
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/task/{task_id}",
            method="DELETE")
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
        # the task is gone, yet its committed spool serves the pages
        stream = PageStream(
            f"http://127.0.0.1:{srv.port}/v1/task/{task_id}",
            client=HttpClient(FAST))
        rows = [r for p in decode_pages(stream.drain(), [DOUBLE])
                for r in p.to_pylist()]
        exp = LocalEngine(TpchConnector(SF)).execute_sql(
            "select sum(l_extendedprice * l_discount) from lineitem "
            "where l_shipdate >= date '1995-01-01' "
            "and l_shipdate < date '1996-01-01' "
            "and l_discount between 0.05 and 0.07 "
            "and l_quantity < 24")
        assert len(rows) == 1
        assert abs(rows[0][0] - exp[0][0]) <= 1e-6 * abs(exp[0][0])
    finally:
        srv.stop()


# ------------------------------- a FAILED attempt never ends a stream

def test_failed_task_refuses_its_consumers_under_task_retry(tmp_path):
    """retry_policy=TASK re-plans a FAILED task as attempt N+1 while
    its consumers keep running, so what the failed attempt tells them
    decides the rows: its buffers must refuse (retryably) and never
    answer `complete`. A consumer that polled between the failure and
    the coordinator's re-plan used to read a clean, empty end of stream
    and finish without the producer's rows (the missing nation rows of
    test_spool_chaos's kill matrix). Once the replacement attempt has
    committed, the failed attempt's location serves ITS spool."""
    from presto_tpu.protocol import structs as S
    from presto_tpu.server import TpuWorkerServer
    from tests.protocol_fixtures import (
        call, fragment, task_update_request, var,
    )

    scfg = SpoolConfig(enabled=True, base_dir=str(tmp_path / "spool"),
                       sweep_on_start=False)
    srv = TpuWorkerServer(TpchConnector(SF), spool_config=scfg).start()
    base = f"http://127.0.0.1:{srv.port}/v1/task"
    try:
        # a middle-stage task whose one producer is gone and left no
        # spool: its pull fails and the task with it, with no timing
        rev = var("revenue", "double")
        remote = S.RemoteSourceNode(id="0", sourceFragmentIds=["0"],
                                    outputVariables=[rev])
        keep = call("GREATER_THAN_OR_EQUAL",
                    "$operator$greater_than_or_equal", "boolean",
                    [rev, rev], ["double", "double"])
        tur = task_update_request(
            fragment("1", S.FilterNode(id="1", source=remote,
                                       predicate=keep), [rev], ["0"]),
            n_splits=0, sf=SF,
            session_properties={"retry_policy": "TASK"})
        tur.sources = [S.TaskSource(
            planNodeId="0",
            splits=[S.ScheduledSplit(
                sequenceId=0, planNodeId="0",
                split=S.Split(connectorId="$remote", connectorSplit={
                    "location": f"{base}/qf.0.0.0.0",
                    "bufferId": "0"}))],
            noMoreSplits=True)]
        req = urllib.request.Request(
            f"{base}/qf.1.0.0.0", data=tur.dumps().encode(),
            method="POST", headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
        state = "PLANNED"
        while state in ("PLANNED", "RUNNING"):
            req = urllib.request.Request(
                f"{base}/qf.1.0.0.0/status",
                headers={"X-Presto-Current-State": state,
                         "X-Presto-Max-Wait": "1s"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                state = json.loads(resp.read())["state"]
        assert state == "FAILED"

        consumer = PageStream(f"{base}/qf.1.0.0.0",
                              client=HttpClient(FAST))
        with pytest.raises(OSError):
            consumer.fetch()
        assert not consumer.complete and consumer.token == 0

        # attempt 1 of the same work unit commits (another worker's, on
        # the shared spool base): the failed attempt's location now
        # serves those frames from token 0
        frames = [_frame(b"replacement" * 3), _frame(b"attempt")]
        _commit_task(srv.task_manager.spool, "qf.1.0.0.1", frames)
        assert consumer.drain() == b"".join(frames)
    finally:
        srv.stop()
