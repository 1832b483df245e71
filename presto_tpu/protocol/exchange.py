"""Concurrent pipelined exchange: prefetch ALL upstream locations into
one bounded buffer.

Reference roles: operator/ExchangeClient.java:71,255,322 — the consumer
side of a shuffle opens one PageBufferClient per upstream location and
keeps concurrent sequenced GETs in flight against every one of them,
landing pages in a buffer bounded by maxBufferedBytes; the operator then
drains that buffer in arrival order, so its compute overlaps every
producer's network transfer. Presto@Meta (VLDB'23 §3) identifies this
fetch/compute overlap as the dominant factor in shuffle-bound stage
latency.

`ExchangeClient` here is that shape over `exchange_client.PageStream`:
one stream (and one fetcher thread) per upstream location, chunks decoded
off the wire by the fetcher and appended to a deque whose byte accounting
enforces `ExchangeConfig.max_buffered_bytes` — a full buffer PARKS the
fetchers on a condition variable, and the consumer's pop wakes them, so
backpressure propagates all the way to the producers' un-acknowledged
token cursors. Every page-protocol defense lives in PageStream and
survives unchanged per stream: truncation validation before ack,
`WorkerRestartedError` on a changed task instance id, and token-exact
fallback to a committed spool under retry_policy=TASK.

Consumption order: per-stream FIFO is exact (one fetcher per stream,
one FIFO buffer); ACROSS streams chunks interleave in arrival order,
which is the reference's semantics too — ordered results go through the
coordinator's merge path (`stream_pages` below), never this client."""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Iterator, List, Optional, Sequence, Tuple

from presto_tpu.config import DEFAULT_EXCHANGE, ExchangeConfig
from presto_tpu.obs.metrics import (
    gauge as _gauge, histogram as _histogram,
)
from presto_tpu.protocol.exchange_client import PageStream, decode_pages
from presto_tpu.utils.threads import spawn
from presto_tpu.utils.tracing import TRACER, now

_M_BUF_BYTES_HIGH = _gauge(
    "presto_tpu_exchange_buffered_bytes_high_water",
    "Max bytes ever held in an ExchangeClient's in-flight buffer")
_M_BUF_DEPTH_HIGH = _gauge(
    "presto_tpu_exchange_buffer_depth_high_water",
    "Max chunks ever held in an ExchangeClient's in-flight buffer")
_M_STREAMS = _gauge(
    "presto_tpu_exchange_concurrent_streams",
    "Upstream page streams currently being fetched concurrently")
_M_FETCH_WAIT = _histogram(
    "presto_tpu_exchange_fetch_wait_seconds",
    "Time fetcher threads spent parked on a full exchange buffer")
_M_CONSUMER_WAIT = _histogram(
    "presto_tpu_exchange_consumer_wait_seconds",
    "Time consumers spent blocked on an empty exchange buffer")


def exchange_counters() -> dict:
    """Snapshot of the process-wide exchange metrics (the coordinator
    diffs two snapshots around a query for the EXPLAIN ANALYZE line)."""
    from presto_tpu.protocol.exchange_client import (
        _M_BYTES, _M_FETCHES, _M_PAGES, _M_TRUNCATED,
    )
    return {
        "fetches": int(_M_FETCHES.value()),
        "pages": int(_M_PAGES.value()),
        "bytes": int(_M_BYTES.value()),
        "truncations": int(_M_TRUNCATED.value()),
        "buffered_bytes_high_water": int(_M_BUF_BYTES_HIGH.value()),
        "buffer_depth_high_water": int(_M_BUF_DEPTH_HIGH.value()),
    }


class ExchangeClient:
    """Pull N upstream buffers concurrently through one bounded buffer.

    `locations` is a sequence of (task_results_uri, buffer_id) pairs —
    exactly the shape of a task's remote splits. With `types` set, the
    fetcher threads also DECODE wire frames into engine pages (decode
    overlaps the consumer's compute), and iteration yields
    ``List[Page]`` chunks; without it, raw frame ``bytes``. Byte
    accounting always uses wire size, so the buffer bound means the
    same thing either way.

    The consumer API is a blocking iterator: ``for chunk in client``
    (or ``next_chunk()`` returning None at end-of-streams). The first
    fetcher error is re-raised on the consumer thread fail-fast;
    sibling fetchers are aborted rather than drained. Use as a context
    manager so an early exit (error mid-consume) still releases the
    upstream buffers via DELETE."""

    def __init__(self, locations: Sequence[Tuple[str, str]],
                 types=None,
                 config: Optional[ExchangeConfig] = None,
                 client=None, spool=None):
        self.config = config or DEFAULT_EXCHANGE
        self.types = list(types) if types is not None else None
        self._streams = [
            PageStream(loc, buffer_id=buf,
                       max_wait=self.config.max_wait,
                       max_size_bytes=self.config.max_response_bytes,
                       client=client, spool=spool)
            for loc, buf in locations]
        self._cond = threading.Condition()
        self._buf: "deque[Tuple[int, object]]" = deque()
        self._buffered_bytes = 0
        self._open_streams = len(self._streams)
        self._error: Optional[BaseException] = None
        self._closed = False
        #: instance high-water marks (the per-query observability the
        #: bounded-buffer test asserts against; the module gauges keep
        #: the process-wide max)
        self.buffered_bytes_high_water = 0
        self.buffer_depth_high_water = 0
        #: wire bytes landed so far (the `bytes` of the consumer's
        #: `exchange_wait` and `collect_root` spans)
        self.bytes_pulled = 0
        # the trace and span this client pulls for: the fetcher threads
        # record their pulls and decodes under it (they install no trace
        # scope, so their GETs stay off the query's RPC timeline)
        self._trace = TRACER.here()
        # at most this many GETs in flight across all streams; the
        # permit wraps ONLY the network fetch, never the buffer wait —
        # a parked fetcher must not starve other streams of permits
        self._permits = (
            threading.BoundedSemaphore(self.config.max_concurrent_fetchers)
            if self.config.max_concurrent_fetchers > 0 else None)
        self._threads = [
            spawn("exchange", f"fetch-{i}", self._fetch_loop, args=(s,),
                  start=False)
            for i, s in enumerate(self._streams)]
        for t in self._threads:
            t.start()

    # ------------------------------------------------------- fetcher side
    def _fetch_loop(self, stream: PageStream) -> None:
        _M_STREAMS.inc()
        try:
            while not stream.complete:
                with self._cond:
                    if self._closed or self._error is not None:
                        return
                if self._permits is not None:
                    self._permits.acquire()
                try:
                    t0 = now()
                    data = stream.fetch()
                finally:
                    if self._permits is not None:
                        self._permits.release()
                if data:
                    self._pulled(t0, len(data))
                    payload = (self._decode(data)
                               if self.types is not None else data)
                    if not self._offer(len(data), payload):
                        return
        except BaseException as e:   # noqa: BLE001 — re-raised on consumer
            with self._cond:
                if self._error is None:
                    self._error = e
                self._cond.notify_all()
        finally:
            _M_STREAMS.dec()
            stream.close()
            with self._cond:
                self._open_streams -= 1
                self._cond.notify_all()

    def _pulled(self, start: float, nbytes: int) -> None:
        """An `exchange_pull` span for a GET that landed data, recorded
        when it ends: a long poll that comes back empty was a wait for
        the producer, not a pull (the consumer's `exchange_wait` holds
        it), and which of the two a GET is shows only in its answer."""
        here = self._trace
        TRACER.record(here.trace_id if here else None, "exchange_pull",
                      start, now(),
                      parent_id=here.parent_span_id if here else "",
                      mark=True, bytes=nbytes)

    def _decode(self, data: bytes) -> List:
        here = self._trace
        with TRACER.span(here.trace_id if here else None, "deserialize",
                         parent_id=here.parent_span_id if here else None,
                         bytes=len(data)):
            return decode_pages(data, self.types)

    def _offer(self, nbytes: int, payload) -> bool:
        """Land one chunk in the buffer, parking while it is full.
        Admission rule: wait while the buffer is NON-EMPTY and this
        chunk would push it past `max_buffered_bytes` — an empty buffer
        always admits, so one oversized chunk can never deadlock the
        pipeline (the bound is then max(cap, that chunk)). Returns
        False when the client closed/failed while parked."""
        t0 = time.perf_counter()
        with self._cond:
            while (not self._closed and self._error is None
                   and self._buf
                   and self._buffered_bytes + nbytes
                   > self.config.max_buffered_bytes):
                self._cond.wait()
            if self._closed or self._error is not None:
                return False
            self._buf.append((nbytes, payload))
            self._buffered_bytes += nbytes
            self.bytes_pulled += nbytes
            if self._buffered_bytes > self.buffered_bytes_high_water:
                self.buffered_bytes_high_water = self._buffered_bytes
            if len(self._buf) > self.buffer_depth_high_water:
                self.buffer_depth_high_water = len(self._buf)
            self._cond.notify_all()
        _M_FETCH_WAIT.observe(time.perf_counter() - t0)
        _M_BUF_BYTES_HIGH.set_max(self.buffered_bytes_high_water)
        _M_BUF_DEPTH_HIGH.set_max(self.buffer_depth_high_water)
        return True

    # ------------------------------------------------------ consumer side
    def next_chunk(self):
        """Blocking pop in arrival order: the next ``List[Page]`` (or
        raw ``bytes`` without `types`), or None once every stream
        completed and the buffer drained. The first fetcher error is
        raised here after aborting the remaining streams."""
        t0 = time.perf_counter()
        err = None
        out = None
        with self._cond:
            while True:
                if self._error is not None:
                    err = self._error
                    break
                if self._buf:
                    nbytes, out = self._buf.popleft()
                    self._buffered_bytes -= nbytes
                    self._cond.notify_all()
                    break
                if self._open_streams == 0 or self._closed:
                    break
                self._cond.wait()
        _M_CONSUMER_WAIT.observe(time.perf_counter() - t0)
        if err is not None:
            self.close()
            raise err
        return out

    def __iter__(self) -> Iterator:
        while True:
            chunk = self.next_chunk()
            if chunk is None:
                return
            yield chunk

    def pages(self) -> Iterator:
        """Alias for iteration (the ExchangeClient.java pollPage shape)."""
        return iter(self)

    def drain_pages(self) -> List:
        """Everything, flattened (requires `types`)."""
        out: List = []
        for chunk in self:
            out.extend(chunk)
        return out

    # ------------------------------------------------------------ teardown
    def close(self) -> None:
        """Stop fetchers, drop buffered chunks, release upstream
        buffers. Idempotent; safe from any thread."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._buf.clear()
            self._buffered_bytes = 0
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)

    def __enter__(self) -> "ExchangeClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def stream_pages(location: str, buffer_id: str = "0", types=None, *,
                 client=None, spool=None,
                 max_size_bytes: Optional[int] = None,
                 max_wait: str = "1s") -> Iterator:
    """Serial fetch→decode→yield over ONE upstream buffer, preserving
    exact page order — the ordered-merge collect (`cluster._merge_root`)
    needs per-stream order and applies its own bounded-queue
    backpressure, so it rides this instead of the concurrent client.
    Yields engine Pages with `types`, raw frame bytes without."""
    stream = PageStream(location, buffer_id=buffer_id, max_wait=max_wait,
                        max_size_bytes=max_size_bytes, client=client,
                        spool=spool)
    try:
        while not stream.complete:
            data = stream.fetch()
            if not data:
                continue
            if types is None:
                yield data
            else:
                with TRACER.span(None, "deserialize", bytes=len(data)):
                    pages = decode_pages(data, list(types))
                yield from pages
    finally:
        stream.close()
