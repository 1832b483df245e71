"""Serving tier — the event-loop front door + keep-alive wire plane.

Reference roles: the native worker's libevent HTTP shell
(presto_cpp/main/http/HttpServer.cpp — one event loop parks thousands
of mostly-idle long-poll connections) and Jetty's selector threads under
the Java coordinator, paired with HttpClient's pooled keep-alive
connections on the client side (InternalCommunicationConfig). The
statement protocol and the task result protocol are both long-poll
shaped (PAPER L0/L1: StatementClientV1 nextUri polling, workers
streaming pages), which is exactly the workload thread-per-connection
serves worst.

Layout:

  net/aio_server.py   asyncio event-loop HTTP server (both node roles)

The connection pool itself lives in `protocol/transport.py` (the single
RPC chokepoint); it shares this package's metrics so one scrape shows
both sides of every keep-alive connection.

Every serving-tier metric is registered HERE — one call site per name
(metric-name-grammar rule) covering the server loops and the client
pool via the `role` label.
"""

from presto_tpu.obs.metrics import (
    counter as _counter, gauge as _gauge, histogram as _histogram,
)

#: open connections by role: "worker"/"coordinator" count accepted
#: server-side sockets, "client-pool" counts pooled outbound sockets
M_OPEN_CONNECTIONS = _gauge(
    "presto_tpu_net_open_connections",
    "Currently open serving-tier connections, by role (server loops "
    "count accepted sockets; client-pool counts live pooled outbound "
    "connections)", ("role",))
M_CONNECTIONS_OPENED = _counter(
    "presto_tpu_net_connections_opened_total",
    "Connections opened, by role (server accepts / client pool dials)",
    ("role",))
M_KEEPALIVE_REUSE = _counter(
    "presto_tpu_net_keepalive_reuse_total",
    "Requests served or sent over an already-open keep-alive "
    "connection instead of a fresh dial, by role", ("role",))
#: sub-MILLISECOND-resolved buckets: a healthy loop overshoots its
#: timer by tens of microseconds, so the default 1ms-floor bucket set
#: collapsed every healthy tick into one bin and the p99 could not
#: distinguish "idle loop" from "1ms of blocking per tick". Anything
#: past ~100ms still means blocking work ran on the loop.
M_LOOP_LAG = _histogram(
    "presto_tpu_net_event_loop_lag_seconds",
    "Observed event-loop timer overshoot per heartbeat tick (a "
    "blocked-loop detector: large values mean blocking work ran on "
    "the loop)",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.025,
             0.1, 0.5, 2.5))
M_SENDFILE_BYTES = _counter(
    "presto_tpu_net_sendfile_bytes_total",
    "Result bytes served zero-copy from committed spool files via "
    "os.sendfile (or the loop's fallback path)")
