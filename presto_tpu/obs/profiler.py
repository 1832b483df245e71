"""Always-on sampling profiler.

Reference role: the native worker's periodic stack sampler feeding
per-query CPU attribution (and, operationally, async-profiler style
collapsed stacks). Python can snapshot every thread's frame cheaply via
`sys._current_frames()`, so the profiler is a single ~100 Hz sampler
thread that buckets samples three ways:

  - role/purpose from the PR 7 thread-name discipline
    (`presto-tpu-<role>-<purpose>-<n>`, utils/threads.spawn)
  - the query each thread is serving, via the tid -> trace-id mirror
    maintained by utils/tracing.trace_scope
  - the stack itself, collapsed to `file:func;file:func;...`

Memory is bounded two ways: stacks are capped at `profiler_max_depth`
leaf-side frames, and each (role, purpose, query) bucket keeps at most
`profiler_top_k` distinct stacks (min-count eviction, evictions
counted). Overhead is bounded by construction: each cycle sleeps at
least sample_cost / `profiler_max_overhead`, so sampling can never eat
more than that fraction of wall clock — measured and exposed as
`overhead_fraction()`.

Surfaces: `system.runtime.profile` rows, `GET /v1/profile` (collapsed-
stack text, flamegraph-ready), and EXPLAIN ANALYZE's "Profile:" line.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from presto_tpu.config import DEFAULT_OBS
from presto_tpu.obs.metrics import counter, gauge
from presto_tpu.utils.tracing import thread_traces

_M_SAMPLES = counter("presto_tpu_profiler_samples_total",
                     "Stack samples taken by the sampling profiler")
_M_SELF_SECONDS = counter(
    "presto_tpu_profiler_self_seconds_total",
    "Wall seconds the profiler spent taking samples")
_M_BUCKETS = gauge("presto_tpu_profiler_buckets",
                   "Distinct (role, purpose, query) profile buckets")
_M_DROPPED = counter(
    "presto_tpu_profiler_dropped_stacks_total",
    "Distinct stacks evicted by the per-bucket top-K cap")

_NAME_PREFIX = "presto-tpu-"


def _parse_thread_name(name: str) -> Tuple[str, str]:
    """`presto-tpu-<role>-<purpose>-<n>` -> (role, purpose); anything
    else buckets under role "other" so foreign threads stay visible."""
    if not name.startswith(_NAME_PREFIX):
        return "other", name
    rest = name[len(_NAME_PREFIX):]
    head, _, tail = rest.rpartition("-")
    if head and tail.isdigit():
        rest = head
    role, _, purpose = rest.partition("-")
    return role or "other", purpose or "-"


class SamplingProfiler:
    def __init__(self, hz: Optional[float] = None,
                 top_k: Optional[int] = None,
                 max_depth: Optional[int] = None,
                 max_overhead: Optional[float] = None):
        self.hz = float(hz if hz is not None else DEFAULT_OBS.profiler_hz)
        self.top_k = int(top_k if top_k is not None
                         else DEFAULT_OBS.profiler_top_k)
        self.max_depth = int(max_depth if max_depth is not None
                             else DEFAULT_OBS.profiler_max_depth)
        self.max_overhead = float(
            max_overhead if max_overhead is not None
            else DEFAULT_OBS.profiler_max_overhead)
        self._lock = threading.Lock()
        # (role, purpose, query_id | None) -> {collapsed stack: count}
        self._buckets: Dict[Tuple[str, str, Optional[str]],
                            Dict[str, int]] = {}
        self._samples = 0
        self._dropped = 0
        self._self_seconds = 0.0
        self._started_at: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ lifecycle
    def ensure_started(self) -> bool:
        """Idempotent start (server constructors call this; the
        no-spawn-in-request-handler rule keeps it out of handlers).
        Returns whether the sampler is running."""
        if not DEFAULT_OBS.profiler_enabled:
            return False
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return True
            self._stop.clear()
            if self._started_at is None:
                self._started_at = time.time()
            from presto_tpu.utils.threads import spawn
            self._thread = spawn("obs", "profiler", self._run)
            return True

    def stop(self) -> None:
        t = self._thread
        self._stop.set()
        if t is not None:
            t.join(timeout=2.0)
        with self._lock:
            self._thread = None

    def _run(self) -> None:
        period = 1.0 / max(self.hz, 1.0)
        while not self._stop.is_set():
            t0 = time.time()
            try:
                self._sample_once()
            except Exception:   # noqa: BLE001 — the sampler must survive anything
                pass
            dt = time.time() - t0
            with self._lock:
                self._self_seconds += dt
            _M_SELF_SECONDS.inc(dt)
            # overhead bound by construction: the sleep is always at
            # least sample_cost / max_overhead
            self._stop.wait(max(period, dt / max(self.max_overhead,
                                                 1e-4)))

    # ------------------------------------------------------------- sampling
    def _sample_once(self) -> None:
        frames = sys._current_frames()
        # Not this thread's own frame: it holds `frames`, and `frames`
        # would hold it -- a reference cycle around EVERY thread's stack,
        # locals and all, a hundred times a second. Until the cyclic
        # collector came by, each device page a worker thread had in
        # hand at a sample stayed on the device (PERF.md, PR 32:
        # `peak_hbm_gb` followed how often the collector ran).
        del frames[threading.get_ident()]
        names = {t.ident: t.name for t in threading.enumerate()}
        traces = thread_traces()
        with self._lock:
            for tid, frame in frames.items():
                role, purpose = _parse_thread_name(
                    names.get(tid, "?"))
                stack = self._collapse(frame)
                bucket = self._buckets.setdefault(
                    (role, purpose, traces.get(tid)), {})
                if stack in bucket:
                    bucket[stack] += 1
                elif len(bucket) < self.top_k:
                    bucket[stack] = 1
                else:
                    # evict the coldest stack; ties broken arbitrarily
                    victim = min(bucket, key=bucket.get)
                    if bucket[victim] <= 1:
                        del bucket[victim]
                        bucket[stack] = 1
                    self._dropped += 1
                    _M_DROPPED.inc()
                self._samples += 1
            _M_BUCKETS.set(len(self._buckets))
        _M_SAMPLES.inc(len(frames))

    def _collapse(self, frame) -> str:
        parts: List[str] = []
        f = frame
        while f is not None:
            code = f.f_code
            parts.append(f"{os.path.basename(code.co_filename)}"
                         f":{code.co_name}")
            f = f.f_back
        parts.reverse()               # root-first, flamegraph order
        if len(parts) > self.max_depth:
            parts = parts[-self.max_depth:]   # keep the leaf side
        return ";".join(p.replace(";", ",") for p in parts)

    # ------------------------------------------------------------- readout
    def rows(self) -> List[tuple]:
        """(role, purpose, query_id, stack, samples) rows for
        system.runtime.profile."""
        with self._lock:
            return [(role, purpose, qid, stack, count)
                    for (role, purpose, qid), bucket in
                    self._buckets.items()
                    for stack, count in bucket.items()]

    def collapsed(self, limit: int = 2000) -> str:
        """Collapsed-stack text (`role;purpose;qid;frames... count` per
        line) — pipe straight into flamegraph.pl / speedscope."""
        rows = sorted(self.rows(), key=lambda r: -r[4])[:limit]
        return "\n".join(
            f"{role};{purpose};{qid or '-'};{stack} {count}"
            for role, purpose, qid, stack, count in rows)

    def overhead_fraction(self) -> float:
        with self._lock:
            if self._started_at is None:
                return 0.0
            elapsed = time.time() - self._started_at
            return (self._self_seconds / elapsed) if elapsed > 0 else 0.0

    def stats(self) -> dict:
        with self._lock:
            return {"samples": self._samples,
                    "buckets": len(self._buckets),
                    "dropped": self._dropped,
                    "running": (self._thread is not None
                                and self._thread.is_alive())}

    def reset(self) -> None:
        with self._lock:
            self._buckets.clear()
            self._samples = 0
            self._dropped = 0
            self._self_seconds = 0.0
            self._started_at = time.time()


#: process-wide profiler (the Guice-singleton analog); servers call
#: PROFILER.ensure_started() from their constructors
PROFILER = SamplingProfiler()
