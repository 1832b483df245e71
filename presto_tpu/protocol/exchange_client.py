"""Exchange client — the pull side of the page-stream protocol.

Reference roles: operator/ExchangeClient.java:71,255,322 +
presto_cpp/main/PrestoExchangeSource.cpp: sequenced GET
/v1/task/{id}/results/{buffer}/{token}, acknowledge, DELETE on close; the
X-Presto-* headers carry token progression and completion. This client is
synchronous (one upstream at a time per call site); the worker's own
RemoteSource lowering fans out over upstream locations.

All HTTP rides `protocol/transport.HttpClient` (retries with backoff,
error classification, per-worker circuit breakers). On top of that this
module adds page-protocol-level defenses: a truncated response body
(connection dropped mid-transfer, or an injected fault) is detected by
frame validation BEFORE the token is acknowledged, so the same token is
simply re-fetched — the server re-serves un-acknowledged frames, and a
replay can neither skip nor duplicate pages."""

from __future__ import annotations

import struct
from typing import List, Optional, Tuple

from presto_tpu.obs.metrics import counter as _counter
from presto_tpu.protocol.transport import (
    HttpClient, RetriesExhaustedError, TransportError,
    WorkerRestartedError, get_client,
)
from presto_tpu.utils.tracing import TRACER

_M_FETCHES = _counter("presto_tpu_exchange_fetches_total",
                      "Exchange fetch rounds (one sequenced GET each)")
_M_PAGES = _counter("presto_tpu_exchange_pages_total",
                    "SerializedPage frames pulled over the exchange")
_M_BYTES = _counter("presto_tpu_exchange_bytes_total",
                    "Wire bytes pulled over the exchange")
_M_TRUNCATED = _counter(
    "presto_tpu_exchange_truncated_bodies_total",
    "Page-fetch bodies rejected by frame validation and re-fetched")

_FRAME_HEADER = struct.Struct("<ibiiq")     # serde SerializedPage header


def count_frames(data: bytes) -> Optional[int]:
    """Number of whole SerializedPage frames in `data`, or None if the
    body ends mid-frame — walks the 21-byte headers without decoding
    payloads, so a body cut inside a frame (truncation) is caught
    before any token acknowledge."""
    off = 0
    n = len(data)
    count = 0
    while off < n:
        if off + _FRAME_HEADER.size > n:
            return None
        size = _FRAME_HEADER.unpack_from(data, off)[3]
        if size < 0:
            return None
        off += _FRAME_HEADER.size + size
        if off > n:
            return None
        count += 1
    return count


def frames_complete(data: bytes) -> bool:
    """True iff `data` is a whole number of SerializedPage frames."""
    return count_frames(data) is not None


class PageStream:
    """Pull all SerializedPage frames from one upstream buffer.
    `max_size_bytes` bounds each GET's response (client-side backpressure:
    ExchangeClient.java maxResponseSize / PrestoExchangeSource's
    kMaxBytes) so one pull round never materializes more than a chunk."""

    #: replays of one token on truncated bodies before giving up
    TRUNCATION_RETRIES = 4

    def __init__(self, task_uri: str, buffer_id: str = "0",
                 max_wait: str = "1s",
                 max_size_bytes: Optional[int] = None,
                 client: Optional[HttpClient] = None,
                 spool=None):
        self.base = task_uri.rstrip("/")
        self.buffer_id = buffer_id
        self.max_wait = max_wait
        self.max_size_bytes = max_size_bytes
        self.client = client or get_client()
        self.token = 0
        self.complete = False
        self.task_instance_id: Optional[str] = None
        # spooled-exchange fallback (retry_policy=TASK): when the
        # producer's HTTP location dies mid-stream, remaining frames
        # come straight from its committed spool (spool/store.SpoolStore)
        self.spool = spool
        self._committed = None           # CommittedTaskSpool once entered

    def _get(self, url: str, validate: bool = False
             ) -> Tuple[bytes, dict]:
        """One transport GET; with `validate`, a body that does not
        parse as complete frames — or whose frame count disagrees with
        the token advance the server's headers claim — counts as a
        transient failure and the SAME url (same un-acknowledged token)
        is fetched again."""
        headers = {"X-Presto-Max-Wait": self.max_wait}
        if self.max_size_bytes is not None:
            headers["X-Presto-Max-Size"] = f"{self.max_size_bytes}B"
        last: Optional[BaseException] = None
        for _attempt in range(self.TRUNCATION_RETRIES + 1):
            resp = self.client.request(url, headers=headers,
                                       request_class="page_fetch")
            if not validate:
                return resp.body, resp.headers
            problem = self._body_problem(resp)
            if problem is None:
                return resp.body, resp.headers
            _M_TRUNCATED.inc()
            last = TransportError(f"{problem} from {url}")
        raise RetriesExhaustedError(
            f"page body from {url} still truncated after "
            f"{self.TRUNCATION_RETRIES + 1} fetch(es)") from last

    def _body_problem(self, resp) -> Optional[str]:
        """None if the body is intact, else why it must be re-fetched.
        Frame-walking alone misses a truncation landing exactly on a
        frame boundary (the body parses, pages are silently missing),
        so the frame count is also cross-checked against the token
        advance the server claims in X-Presto-Page-End-Sequence-Id."""
        nframes = count_frames(resp.body)
        if nframes is None:
            return "truncated page body"
        end = resp.headers.get("X-Presto-Page-End-Sequence-Id")
        if end is not None and int(end) - self.token != nframes:
            return (f"page body carries {nframes} frame(s) but the "
                    f"token advance claims {int(end) - self.token} "
                    "(truncated on a frame boundary)")
        return None

    def fetch(self) -> bytes:
        """One round: GET next frames, acknowledge, advance the token.
        With a spool store attached, a dead producer location falls
        back to its committed spool AT THE CURRENT TOKEN — frames
        acknowledged over HTTP are never re-served, frames not yet
        acknowledged come from the spool exactly once."""
        if self._committed is not None:
            return self._fetch_spool()
        url = f"{self.base}/results/{self.buffer_id}/{self.token}"
        try:
            body, headers = self._get(url, validate=True)
        except OSError:
            if self._enter_spool():
                return self._fetch_spool()
            raise
        _M_FETCHES.inc()
        _M_BYTES.inc(len(body))
        _M_PAGES.inc(count_frames(body) or 0)
        instance = headers.get("X-Presto-Task-Instance-Id")
        if self.task_instance_id is None:
            self.task_instance_id = instance
        elif instance != self.task_instance_id:
            # a restarted worker serves a DIFFERENT task instance — the
            # committed spool (if any) is the only consistent source
            if self._enter_spool():
                return self._fetch_spool()
            raise WorkerRestartedError(
                f"task instance changed mid-stream on {self.base} "
                "(worker restarted)")
        nxt = int(headers.get("X-Presto-Page-End-Sequence-Id",
                              self.token))
        self.complete = (headers.get("X-Presto-Buffer-Complete",
                                     "false") == "true")
        if nxt > self.token:
            # token-sequenced GETs are idempotent: the server re-serves
            # un-acknowledged frames, so everything up to here is safe
            # to replay; the ack is what advances the server cursor.
            # The token advances BEFORE the ack round-trip — a worker
            # dying between body and ack must not make the spool
            # fallback replay frames this consumer already holds.
            self.token = nxt
            try:
                self._get(f"{self.base}/results/{self.buffer_id}/{nxt}"
                          f"/acknowledge")
            except OSError:
                if self.spool is None:
                    raise
                # spool mode: the committed spool needs no ack cursor
        return body

    def _enter_spool(self) -> bool:
        """Switch this stream onto the producer's committed spool (any
        attempt), validating the part file against its manifest — a
        truncated or corrupt spool raises SpoolIntegrityError instead
        of silently under-serving. False when no spool store is
        attached or nothing committed (caller re-raises the transport
        error)."""
        if self.spool is None:
            return False
        committed = self.spool.find_committed_for_location(self.base)
        if committed is None:
            return False
        from presto_tpu.spool.store import record_fallback_read
        record_fallback_read()
        self._committed = committed
        return True

    def _fetch_spool(self) -> bytes:
        frames = self._committed.frames(self.buffer_id,
                                        start=self.token)
        out, size = [], 0
        cap = self.max_size_bytes or (16 << 20)
        for f in frames:
            if out and size + len(f) > cap:
                break
            out.append(f)
            size += len(f)
        _M_FETCHES.inc()
        _M_BYTES.inc(size)
        _M_PAGES.inc(len(out))
        self.token += len(out)
        self.complete = (self.token
                         >= self._committed.frame_count(self.buffer_id))
        return b"".join(out)

    def close(self):
        """Release the buffer (reference: abortResults DELETE); a
        spool-served stream has no live buffer to release."""
        if self._committed is not None:
            return
        try:
            self.client.delete(f"{self.base}/results/{self.buffer_id}")
        except Exception:            # noqa: BLE001 — abort is best-effort
            pass

    def drain(self) -> bytes:
        chunks = []
        while not self.complete:
            chunks.append(self.fetch())
        self.close()
        return b"".join(chunks)

    def drain_pages(self, types, sink) -> None:
        """Bounded-memory drain: decode each fetched chunk into engine
        pages immediately and hand them to `sink(page)` — raw wire bytes
        never accumulate beyond one chunk."""
        while not self.complete:
            data = self.fetch()
            for p in decode_pages(data, list(types)):
                sink(p)
        self.close()


def decode_pages(data: bytes, types) -> List:
    """Concatenated wire frames -> engine Pages of an exchange: host
    pages, over numpy arrays, their row counts too. A string column
    keeps its dictionary as it crossed the wire (`sparse`, one object
    for all the pages that name it). A consumer fuses what it pulls
    (`concat_pages_host`, which compacts once and puts the fused page
    on the device: no program takes a page from here as it is), and the
    root reads rows by code. The `deserialize` span around the call
    gets `device_fetches`: the arrays a consumer would have to fetch
    back (none)."""
    from presto_tpu.protocol.serde import (
        decode_serialized_page, note_exchange_pages, wire_blocks_to_page,
    )

    pages = []
    off = 0
    while off < len(data):
        blocks, n, off = decode_serialized_page(data, off)
        pages.append(wire_blocks_to_page(blocks, types, n,
                                         compact_strings=False, host=True))
    TRACER.add("deserialize",
               device_fetches=note_exchange_pages("decode", pages))
    return pages
