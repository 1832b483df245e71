"""Tests for the concurrent pipelined exchange
(protocol/exchange.ExchangeClient).

Covers the four contracts the client exists for:

  - BACKPRESSURE: under a slow consumer, the in-flight buffer's byte
    high-water stays within `ExchangeConfig.max_buffered_bytes` while
    every frame still arrives exactly once, in per-stream order.
  - OVERLAP: with 50 ms injected per-fetch latency (testing/faults.py)
    on 4 upstream locations, the concurrent drain finishes in < 2x the
    single-stream wall time (the serial baseline is ~4x).
  - DEFENSE PRESERVATION: per-location injected truncation and 500s
    replay/retry invisibly; a changed task-instance-id fails fast to
    the consumer as WorkerRestartedError.
  - RECOVERY: a worker killed mid-drain under retry_policy=TASK still
    yields oracle-correct rows through the spool fallback (seeds 0-4).
"""

import math
import re
import sqlite3
import struct
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from presto_tpu.config import ExchangeConfig, TransportConfig
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.protocol.exchange import ExchangeClient
from presto_tpu.protocol.transport import (
    HttpClient, WorkerRestartedError,
)
from presto_tpu.testing import FaultInjector, FaultSpec

FAST = TransportConfig(retry_base_backoff_s=0.001,
                       retry_max_backoff_s=0.01,
                       retry_budget_s=5.0,
                       breaker_failure_threshold=100,
                       breaker_cooldown_s=0.05)

_RESULTS = re.compile(r".*/results/[^/]+/(\d+)(/acknowledge)?$")


def _frame(payload: bytes) -> bytes:
    """A syntactically complete SerializedPage frame (uncompressed,
    unchecked markers) — enough for the framing walk, no decode."""
    return struct.pack("<ibiiq", 1, 0, len(payload), len(payload),
                       0) + payload


def _payload(chunk: bytes) -> bytes:
    """Strip the 21-byte frame header back off (one frame per chunk)."""
    return chunk[21:]


class _UpstreamHandler(BaseHTTPRequestHandler):
    """A real page-protocol producer: serves ONE frame per sequenced
    GET from `server.frames`, honors acknowledge and DELETE. Stateless
    by token, so un-acknowledged replays re-serve identically."""

    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        pass

    def _send(self, body: bytes, headers=None):
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        srv = self.server
        srv.requests.append(("GET", self.path))
        m = _RESULTS.match(self.path)
        if m is None or m.group(2):           # acknowledge (or unknown)
            return self._send(b"")
        token = int(m.group(1))
        frames = srv.frames
        body = frames[token] if token < len(frames) else b""
        end = min(token + 1, len(frames))
        self._send(body, {
            "X-Presto-Task-Instance-Id": srv.instance,
            "X-Presto-Page-End-Sequence-Id": str(end),
            "X-Presto-Buffer-Complete":
                "true" if end >= len(frames) else "false"})

    def do_DELETE(self):
        self.server.requests.append(("DELETE", self.path))
        self._send(b"")


@pytest.fixture
def upstream():
    servers = []

    def make(frames, instance="inst-1"):
        srv = ThreadingHTTPServer(("127.0.0.1", 0), _UpstreamHandler)
        srv.frames = list(frames)
        srv.instance = instance
        srv.requests = []
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        servers.append(srv)
        return srv, (f"http://127.0.0.1:{srv.server_address[1]}"
                     "/v1/task/t0")

    yield make
    for srv in servers:
        srv.shutdown()
        srv.server_close()


# ------------------------------------------------------- backpressure
def test_buffered_bytes_bound_holds_under_slow_consumer(upstream):
    """Fetchers must PARK once buffered wire bytes would exceed the
    cap, and resume as the consumer drains — the high-water mark proves
    the buffer never ran ahead of the bound."""
    frames = [[_frame(f"s{s}f{j:02d}".encode().ljust(1000, b"."))
               for j in range(12)] for s in range(2)]
    locs = [(upstream(frames[s])[1], "0") for s in range(2)]
    cap = 2600                          # ~2.5 one-frame chunks
    cfg = ExchangeConfig(max_buffered_bytes=cap)
    got = []
    with ExchangeClient(locs, config=cfg,
                        client=HttpClient(FAST)) as xc:
        for chunk in xc:
            got.append(_payload(chunk))
            time.sleep(0.005)           # the slow consumer
        assert xc.buffered_bytes_high_water <= cap, \
            (f"buffer ran ahead of max_buffered_bytes: "
             f"{xc.buffered_bytes_high_water} > {cap}")
        assert xc.buffered_bytes_high_water > 0
    # every frame exactly once...
    want = {_payload(f) for fs in frames for f in fs}
    assert set(got) == want and len(got) == len(want)
    # ...and per-stream FIFO order exact (tokens are sequenced)
    for s in range(2):
        mine = [p for p in got if p.startswith(f"s{s}".encode())]
        assert mine == [_payload(f) for f in frames[s]]
    assert REGISTRY.get(
        "presto_tpu_exchange_concurrent_streams").value() == 0


# ------------------------------------------------------------ overlap
def test_four_slow_upstreams_drain_in_max_not_sum_time(upstream):
    """Acceptance gate: with 50 ms injected per-fetch latency
    (testing/faults.py) and 4 upstream locations, the concurrent
    client drains in < 2x single-stream wall time — the serial
    baseline costs ~4x by construction."""
    frames = [[_frame(f"u{u}f{j}".encode().ljust(256, b"x"))
               for j in range(5)] for u in range(4)]
    locs = [(upstream(frames[u])[1], "0") for u in range(4)]
    spec = FaultSpec(latency_rate=1.0, latency_s=0.05)

    def drain(locations, seed):
        client = HttpClient(FAST)
        client.fault_injector = FaultInjector(seed=seed, spec=spec)
        t0 = time.perf_counter()
        with ExchangeClient(locations,
                            client=client) as xc:
            chunks = list(xc)
            assert xc.buffered_bytes_high_water \
                <= xc.config.max_buffered_bytes
        return time.perf_counter() - t0, chunks

    single_t, single_chunks = drain(locs[:1], seed=0)
    all_t, all_chunks = drain(locs, seed=0)
    assert len(single_chunks) == 5 and len(all_chunks) == 20
    assert all_t < 2 * single_t, \
        (f"4 upstreams took {all_t:.2f}s vs single-stream "
         f"{single_t:.2f}s — fetches are not overlapping")


# ------------------------------------------- per-stream defenses survive
def test_injected_truncation_and_500s_replay_correctly(upstream,
                                                       monkeypatch):
    """Truncated bodies are caught by frame validation BEFORE the ack
    and replay the same token; injected 500s ride the transport retry.
    Both must be invisible in the drained data, per location."""
    # Two fetcher threads draw from the one seeded injector in whatever
    # order they run, so the schedule is not the seed's alone: at a
    # truncation rate of 0.4 five in a row on one token (0.4**5, some
    # twenty fetches) exhausted the stream's replays in one run of six
    # (PR 32: 2 of 12, on the parent too). The replay is under test
    # here, not its bound.
    from presto_tpu.protocol.exchange_client import PageStream
    monkeypatch.setattr(PageStream, "TRUNCATION_RETRIES", 16)
    frames = [[_frame(f"s{s}f{j}".encode().ljust(512, b"y"))
               for j in range(8)] for s in range(2)]
    locs = [(upstream(frames[s])[1], "0") for s in range(2)]
    client = HttpClient(FAST)
    inj = FaultInjector(seed=3, spec=FaultSpec(truncate_rate=0.4,
                                               http_500_rate=0.2))
    client.fault_injector = inj
    with ExchangeClient(locs, client=client) as xc:
        got = [_payload(c) for c in xc]
    for s in range(2):
        assert [p for p in got if p.startswith(f"s{s}".encode())] \
            == [_payload(f) for f in frames[s]], f"stream {s} corrupted"
    # the schedule really fired — otherwise this test proves nothing
    assert inj.injected.get("truncate", 0) >= 1
    assert inj.injected.get("http500", 0) >= 1


def test_instance_change_mid_drain_fails_fast(upstream):
    """A restarted producer (new task instance id) with no spool must
    surface WorkerRestartedError on the CONSUMER thread, not hang the
    iterator or silently mix two instances' pages."""
    srv, uri = upstream([_frame(b"a" * 64), _frame(b"b" * 64),
                         _frame(b"c" * 64)])
    flipped = threading.Event()
    orig_do_get = _UpstreamHandler.do_GET

    def flip(handler):
        if handler.server is srv and len(srv.requests) >= 2:
            srv.instance = "inst-RESTARTED"
            flipped.set()
        orig_do_get(handler)

    _UpstreamHandler.do_GET = flip
    try:
        with pytest.raises(WorkerRestartedError):
            with ExchangeClient([(uri, "0")],
                                client=HttpClient(FAST)) as xc:
                for _ in xc:
                    pass
        assert flipped.is_set()
    finally:
        _UpstreamHandler.do_GET = orig_do_get


# ------------------------------------------------- kill + spool fallback
SF = 0.01
DEADLINE_S = 120.0
KILL_AFTER = (4, 8, 13, 19, 26)
ORACLE_SQL = ("select l_returnflag, l_linestatus, count(*), "
              "sum(l_quantity) from lineitem "
              "group by l_returnflag, l_linestatus "
              "order by l_returnflag, l_linestatus")

CHAOS_TRANSPORT = TransportConfig(
    retry_base_backoff_s=0.01, retry_max_backoff_s=0.2,
    retry_budget_s=5.0, breaker_failure_threshold=3,
    breaker_cooldown_s=0.3)


@pytest.fixture(scope="module")
def kill_cluster():
    from presto_tpu.connectors import TpchConnector
    from presto_tpu.server.cluster import TpuCluster
    c = TpuCluster(
        TpchConnector(SF), n_workers=3,
        session_properties={"query_max_execution_time": str(DEADLINE_S),
                            "retry_policy": "TASK"},
        transport_config=CHAOS_TRANSPORT)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def oracle_rows():
    from presto_tpu.connectors import TpchConnector
    conn = TpchConnector(SF)
    db = sqlite3.connect(":memory:")
    page = conn.table("lineitem").page()
    cols = list(page.names)
    db.execute(f"create table lineitem ({', '.join(cols)})")
    db.executemany(
        f"insert into lineitem values ({', '.join('?' * len(cols))})",
        page.to_pylist())
    db.commit()
    want = db.execute(ORACLE_SQL).fetchall()
    db.close()
    return want


def _stabilize(cluster, deadline_s: float = 15.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if len(cluster.check_workers()) == len(cluster.all_worker_uris):
            return
        time.sleep(0.1)
    raise AssertionError("workers not re-admitted after faults cleared")


@pytest.mark.parametrize("seed", range(5))
def test_kill_mid_drain_spool_fallback_rows_correct(
        kill_cluster, oracle_rows, seed):
    """A worker killed while the concurrent client is mid-drain under
    retry_policy=TASK: the affected PageStreams fall back token-exact
    to committed spools / lost tasks re-plan, and the rows must match
    the independent sqlite oracle — not merely a clean failure."""
    from presto_tpu.protocol import transport as _transport
    cluster = kill_cluster
    hosts = sorted(u.split("://", 1)[1] for u in cluster.all_worker_uris)
    victim = hosts[seed % len(hosts)]
    # the victim must look dead to every node: coordinator client AND
    # the process-global client the workers pull pages through
    shared = _transport.get_client()
    try:
        start = time.monotonic()
        # The per-host request count is timing-dependent: a fast run can
        # drain before the victim's ordinal reaches the threshold, which
        # proves nothing either way.  Halve the threshold and re-run
        # until the kill fires (threshold 1 always fires — the victim
        # sees at least its task POST), so every pass is a real
        # kill-mid-query recovery, never a vacuous clean run.
        kill_at = KILL_AFTER[seed]
        while True:
            inj = FaultInjector(seed=seed,
                                spec=FaultSpec(
                                    kill_after={victim: kill_at}),
                                only_hosts={victim})
            cluster.http.fault_injector = inj
            shared.fault_injector = inj
            got = cluster.execute_sql(ORACLE_SQL)
            if inj.injected.get("kill", 0) >= 1:
                break
            assert kill_at > 1, \
                f"seed {seed}: the kill schedule never fired"
            kill_at = max(1, kill_at // 2)
        assert time.monotonic() - start < DEADLINE_S + 60
        assert len(got) == len(oracle_rows)
        for g, w in zip(sorted(got), sorted(oracle_rows)):
            for gc, wc in zip(g, w):
                if isinstance(wc, float) or isinstance(gc, float):
                    assert math.isclose(gc, wc, rel_tol=1e-6,
                                        abs_tol=1e-9), \
                        f"seed {seed}: {g} vs oracle {w}"
                else:
                    assert gc == wc, f"seed {seed}: {g} vs oracle {w}"
    finally:
        cluster.http.fault_injector = None
        shared.fault_injector = None
        inj.revive(victim)
        _stabilize(cluster)
