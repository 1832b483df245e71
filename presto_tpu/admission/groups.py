"""Hierarchical resource groups with weighted-fair (stride) scheduling.

Reference: execution/resourceGroups/InternalResourceGroup.java +
InternalResourceGroupManager (hierarchical groups, per-group
concurrency / queue limits / scheduling weight, selector rules mapping
sessions to groups):

- groups form a tree; a query admitted at a leaf consumes one running
  slot at the leaf *and every ancestor*, so an internal node's
  ``hard_concurrency`` is an aggregate cap over its subtree;
- among backlogged siblings, grants follow stride scheduling: each
  group advances a virtual ``pass`` by ``K / scheduling_weight`` per
  grant, and the scheduler always picks the eligible child with the
  minimum pass — a 2:1 weight ratio yields ~2:1 dispatch throughput
  under saturation;
- per-group ``memory_quota_bytes`` gates admission on the live
  memory-pool reservations of the group's running queries;
- ``queue_timeout_s`` evicts waiters with a QUERY_QUEUE_FULL-class
  error instead of letting them camp forever.

The blocking API: ``acquire(timeout_s)`` blocks
FIFO for a slot or raises :class:`QueryQueueFull`; ``max_queued``
limits only WAITING queries (``max_queued=0`` == run-or-reject); a
free slot admits immediately only when nothing is already waiting
(arrivals never overtake the queue).  The dispatcher uses the async
``offer`` API instead: callbacks fire under the tree lock and must
not block.
"""

from __future__ import annotations

import collections
import contextlib
import re
import threading
import time
from typing import Callable, Deque, Iterable, List, Optional, Tuple

from presto_tpu.obs.metrics import (counter as _counter, gauge as _gauge,
                                    histogram as _histogram)

_M_ADMITTED = _counter("presto_tpu_resource_group_admitted_total",
                       "Queries admitted per resource group", ("group",))
_M_REJECTED = _counter("presto_tpu_resource_group_rejected_total",
                       "Queries rejected (queue full / slot timeout / "
                       "queue-timeout eviction) per resource group",
                       ("group",))
_M_PEAK_QUEUED = _gauge("presto_tpu_resource_group_peak_queued",
                        "High-water mark of queued queries per "
                        "resource group", ("group",))
_M_QUEUE_DEPTH = _gauge("presto_tpu_admission_queue_depth",
                        "Live queued-query count per resource group",
                        ("group",))
_M_RUNNING = _gauge("presto_tpu_admission_running",
                    "Live running-query count per resource group",
                    ("group",))
#: multi-second-skewed buckets: queue waits under load run seconds to
#: minutes, and the default set's 2.5s..120s tail was too coarse to
#: resolve the shed threshold region (shed_queue_wait_p99_s ~ 20s) —
#: these keep sub-second resolution for the healthy case and add real
#: resolution where the SLO lives
_M_QUEUE_WAIT = _histogram("presto_tpu_admission_queue_wait_seconds",
                           "Seconds a query waited in the admission "
                           "queue before dispatch", ("group",),
                           buckets=(0.005, 0.025, 0.1, 0.5, 1.0, 2.5,
                                    5.0, 10.0, 20.0, 45.0, 120.0,
                                    300.0))

#: stride-scheduler constant: per-grant pass advance is K / weight
_STRIDE_K = float(1 << 16)

#: bounded log of (granted_leaf_path, backlogged_leaf_paths) pairs kept
#: per tree root — enough to verify WFQ ratios after a load run
_GRANT_LOG_MAX = 8192


class QueryQueueFull(RuntimeError):
    """Reference: QUERY_QUEUE_FULL StandardErrorCode."""


class _Waiter:
    __slots__ = ("leaf", "query_id", "grant_cb", "reject_cb",
                 "enqueued_at", "deadline", "state")

    def __init__(self, leaf, query_id, grant_cb, reject_cb,
                 enqueued_at, deadline):
        self.leaf = leaf
        self.query_id = query_id
        self.grant_cb = grant_cb
        self.reject_cb = reject_cb
        self.enqueued_at = enqueued_at
        self.deadline = deadline
        self.state = "queued"


class _Slot:
    """Admission grant: releases the slot chain on exit (idempotent)."""

    def __init__(self, group: "ResourceGroup", query_id: Optional[str],
                 queue_wait_s: float):
        self.group = group
        self.query_id = query_id
        self.queue_wait_s = queue_wait_s
        self._released = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def release(self) -> None:
        self.group._release_slot(self)


class _NestedSlot:
    """No-op slot handed out when the calling thread already holds an
    admission grant (the dispatcher admitted the query before handing
    it to the execution pool) — prevents double admission."""

    def __init__(self, group: "ResourceGroup", inner: _Slot):
        self.group = group
        self.query_id = inner.query_id
        self.queue_wait_s = inner.queue_wait_s

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def release(self) -> None:
        pass


_SCOPE = threading.local()


def current_admission() -> Optional[_Slot]:
    """The admission slot held by the current thread, if any."""
    return getattr(_SCOPE, "slot", None)


@contextlib.contextmanager
def admission_scope(slot: _Slot):
    """Mark the current thread as already admitted (dispatcher pool
    threads wrap query execution in this so the engine's own
    ``group.acquire`` becomes a no-op)."""
    prev = getattr(_SCOPE, "slot", None)
    _SCOPE.slot = slot
    try:
        yield slot
    finally:
        _SCOPE.slot = prev


class ResourceGroup:
    """One node in the group tree; a leaf admits queries directly."""

    def __init__(self, name: str, hard_concurrency: int = 4,
                 max_queued: int = 16, scheduling_weight: int = 1,
                 memory_quota_bytes: Optional[int] = None,
                 queue_timeout_s: Optional[float] = None,
                 children: Iterable["ResourceGroup"] = ()):
        if scheduling_weight < 1:
            raise ValueError("scheduling_weight must be >= 1")
        self.name = name
        self.hard_concurrency = hard_concurrency
        self.max_queued = max_queued
        self.scheduling_weight = scheduling_weight
        self.memory_quota_bytes = memory_quota_bytes
        self.queue_timeout_s = queue_timeout_s
        self.parent: Optional[ResourceGroup] = None
        self.children: List[ResourceGroup] = list(children)
        self.stats = {"admitted": 0, "rejected": 0, "peak_queued": 0}
        self._running = 0
        self._running_qids: set = set()
        self._queue: Deque[_Waiter] = collections.deque()
        self._demand = 0          # queued waiters in this subtree
        self._pass = 0.0
        self._stride = _STRIDE_K / float(scheduling_weight)
        # root-only state (shared by the whole tree via _root())
        self._lock = threading.Lock()
        self._memory_pool = None
        # cluster-wide reservations provider (callable -> {qid: bytes})
        # fed from the coordinator's heartbeat scrape of worker pools —
        # when attached, memory quotas gate on CLUSTER usage, not just
        # the coordinator-local pool
        self._cluster_reservations = None
        self.grant_log: Deque[Tuple[str, Tuple[str, ...]]] = \
            collections.deque(maxlen=_GRANT_LOG_MAX)
        for c in self.children:
            c._adopt(self)

    # -- tree plumbing ------------------------------------------------

    def _adopt(self, parent: "ResourceGroup") -> None:
        if self.parent is not None:
            raise ValueError(f"group {self.name} already has a parent")
        self.parent = parent

    def _root(self) -> "ResourceGroup":
        node = self
        while node.parent is not None:
            node = node.parent
        return node

    @property
    def path(self) -> str:
        parts = []
        node: Optional[ResourceGroup] = self
        while node is not None:
            parts.append(node.name)
            node = node.parent
        return ".".join(reversed(parts))

    def walk(self) -> Iterable["ResourceGroup"]:
        yield self
        for c in self.children:
            yield from c.walk()

    def attach_memory_pool(self, pool) -> None:
        """Wire the tree to a :class:`~presto_tpu.exec.memory.MemoryPool`
        so per-group ``memory_quota_bytes`` gates admission."""
        self._root()._memory_pool = pool

    def attach_cluster_reservations(self, provider) -> None:
        """Wire the tree to a cluster-reservations provider — a
        callable returning ``{query_id: reserved_bytes}`` aggregated
        over every worker pool (the coordinator's heartbeat scrape).
        Quotas then gate on cluster-wide usage; the local pool (if any)
        remains a same-process floor for queries the scrape has not
        seen yet."""
        self._root()._cluster_reservations = provider

    # -- admission ----------------------------------------------------

    def offer(self, grant_cb: Callable, reject_cb: Callable,
              query_id: Optional[str] = None) -> _Waiter:
        """Non-blocking admission: grant immediately when the queue is
        empty and capacity is free along the whole chain, enqueue
        otherwise, or raise :class:`QueryQueueFull` when the queue is
        full.  ``grant_cb(slot)`` / ``reject_cb(exc)`` fire under the
        tree lock — they must not block."""
        if self.children:
            raise ValueError(f"group {self.name} is not a leaf")
        root = self._root()
        now = time.monotonic()
        deadline = (now + self.queue_timeout_s
                    if self.queue_timeout_s is not None else None)
        w = _Waiter(self, query_id, grant_cb, reject_cb, now, deadline)
        with root._lock:
            root._evict_expired_locked(now)
            if not self._queue and root._chain_eligible_locked(self):
                root._grant_locked(self, w, now)
                return w
            if len(self._queue) >= self.max_queued:
                self._count_rejected_locked()
                raise QueryQueueFull(
                    f"group {self.path}: {len(self._queue)} queued "
                    f">= max_queued {self.max_queued}")
            self._enqueue_locked(w)
            # capacity may have freed since the last scheduling event
            # (e.g. memory released mid-query) — try to drain
            root._schedule_locked(now)
        return w

    def acquire(self, timeout_s: Optional[float] = None,
                query_id: Optional[str] = None):
        """Blocking admission (legacy API): FIFO-wait for a slot, or
        raise :class:`QueryQueueFull` on queue overflow / timeout /
        queue-timeout eviction.  Returns a no-op slot when the calling
        thread was already admitted by the dispatcher."""
        held = current_admission()
        if held is not None:
            return _NestedSlot(self, held)
        granted: list = []
        ev = threading.Event()

        def _grant(slot):
            granted.append(slot)
            ev.set()

        def _reject(exc):
            granted.append(exc)
            ev.set()

        w = self.offer(_grant, _reject, query_id=query_id)
        ev.wait(timeout=timeout_s)
        root = self._root()
        with root._lock:
            if w.state == "queued":
                # timed out while queued: withdraw, releasing the
                # queue slot so later arrivals are not pushed out
                self._dequeue_locked(w)
                self._count_rejected_locked()
                w.state = "rejected"
        if granted and isinstance(granted[0], _Slot):
            return granted[0]
        if granted and isinstance(granted[0], BaseException):
            raise granted[0]
        raise QueryQueueFull(
            f"group {self.path}: no slot within {timeout_s}s")

    def withdraw(self, w: _Waiter) -> bool:
        """Remove a still-queued waiter (query cancelled while
        waiting).  Returns True when the waiter was withdrawn, False
        when it had already been granted or rejected."""
        root = self._root()
        with root._lock:
            if w.state != "queued":
                return False
            self._dequeue_locked(w)
            w.state = "withdrawn"
            return True

    # -- locked internals (all run under the tree-root lock) ----------

    def _chain_eligible_locked(self, leaf: "ResourceGroup") -> bool:
        node: Optional[ResourceGroup] = leaf
        while node is not None:
            if node._running >= node.hard_concurrency:
                return False
            if node._over_memory_quota_locked():
                return False
            node = node.parent
        return True

    def _over_memory_quota_locked(self) -> bool:
        if self.memory_quota_bytes is None:
            return False
        root = self._root()
        pool = root._memory_pool
        provider = root._cluster_reservations
        if pool is None and provider is None:
            return False
        cluster: dict = {}
        if provider is not None:
            try:
                cluster = provider() or {}
            except Exception:    # noqa: BLE001 — a failed scrape must
                cluster = {}     # never wedge admission
        reserved = 0
        for q in self._running_qids:
            if q is None:
                continue
            local = pool.query_reserved(q) if pool is not None else 0
            # the scrape lags task admission by one heartbeat — take
            # the larger of the gossiped and same-process views
            reserved += max(int(cluster.get(q, 0)), local)
        return reserved >= self.memory_quota_bytes

    def _enqueue_locked(self, w: _Waiter) -> None:
        self._queue.append(w)
        self.stats["peak_queued"] = max(self.stats["peak_queued"],
                                        len(self._queue))
        _M_PEAK_QUEUED.set_max(self.stats["peak_queued"], group=self.path)
        _M_QUEUE_DEPTH.set(len(self._queue), group=self.path)
        node: Optional[ResourceGroup] = self
        while node is not None:
            if node._demand == 0 and node.parent is not None:
                # waking from dormancy: forfeit banked credit so a
                # long-idle group cannot monopolise the scheduler
                active = [c._pass for c in node.parent.children
                          if c._demand > 0 and c is not node]
                if active:
                    node._pass = max(node._pass, min(active))
            node._demand += 1
            node = node.parent

    def _dequeue_locked(self, w: _Waiter) -> None:
        self._queue.remove(w)
        _M_QUEUE_DEPTH.set(len(self._queue), group=self.path)
        node: Optional[ResourceGroup] = self
        while node is not None:
            node._demand -= 1
            node = node.parent

    def _count_rejected_locked(self) -> None:
        self.stats["rejected"] += 1
        _M_REJECTED.inc(group=self.path)

    def _grant_locked(self, leaf: "ResourceGroup", w: _Waiter,
                      now: float) -> None:
        root = self
        w.state = "granted"
        wait_s = max(0.0, now - w.enqueued_at)
        node: Optional[ResourceGroup] = leaf
        while node is not None:
            node._running += 1
            if w.query_id is not None:
                node._running_qids.add(w.query_id)
            if node.parent is not None:
                node._pass += node._stride
            node = node.parent
        leaf.stats["admitted"] += 1
        _M_ADMITTED.inc(group=leaf.path)
        _M_RUNNING.set(leaf._running, group=leaf.path)
        _M_QUEUE_WAIT.observe(wait_s, group=leaf.path)
        backlogged = tuple(g.path for g in root.walk()
                           if not g.children and g._queue)
        root.grant_log.append((leaf.path, backlogged))
        slot = _Slot(leaf, w.query_id, wait_s)
        w.grant_cb(slot)

    def _release_slot(self, slot: _Slot) -> None:
        root = self._root()
        with root._lock:
            if slot._released:
                return
            slot._released = True
            node: Optional[ResourceGroup] = self
            while node is not None:
                node._running -= 1
                if slot.query_id is not None:
                    node._running_qids.discard(slot.query_id)
                node = node.parent
            _M_RUNNING.set(self._running, group=self.path)
            root._schedule_locked(time.monotonic())

    def _evict_expired_locked(self, now: float) -> None:
        for leaf in self.walk():
            if leaf.children or not leaf._queue:
                continue
            expired = [w for w in leaf._queue
                       if w.deadline is not None and now >= w.deadline]
            for w in expired:
                leaf._dequeue_locked(w)
                leaf._count_rejected_locked()
                w.state = "rejected"
                w.reject_cb(QueryQueueFull(
                    f"group {leaf.path}: queued "
                    f"{now - w.enqueued_at:.3f}s > queue_timeout "
                    f"{leaf.queue_timeout_s}s"))

    def _schedule_locked(self, now: float) -> None:
        self._evict_expired_locked(now)
        while True:
            leaf = self._pick_locked()
            if leaf is None:
                return
            w = leaf._queue.popleft()
            _M_QUEUE_DEPTH.set(len(leaf._queue), group=leaf.path)
            node: Optional[ResourceGroup] = leaf
            while node is not None:
                node._demand -= 1
                node = node.parent
            self._grant_locked(leaf, w, now)

    def _pick_locked(self) -> Optional["ResourceGroup"]:
        """Descend the tree stride-wise to the backlogged, eligible
        leaf the scheduler should grant next (None when blocked)."""
        if self._running >= self.hard_concurrency:
            return None
        if self._over_memory_quota_locked():
            return None
        if not self.children:
            return self if self._queue else None
        for c in sorted((c for c in self.children if c._demand > 0),
                        key=lambda c: (c._pass, c.name)):
            leaf = c._pick_locked()
            if leaf is not None:
                return leaf
        return None

    # -- introspection ------------------------------------------------

    def snapshot(self) -> dict:
        """Live stats row for ``/v1/status`` and ``info()``."""
        d = dict(self.stats)
        d["queued"] = len(self._queue)
        d["running"] = self._running
        d["weight"] = self.scheduling_weight
        return d


class Selector:
    """First-match rule (reference: StaticSelector user/source regexes)."""

    def __init__(self, group: str, user_regex: Optional[str] = None,
                 source_regex: Optional[str] = None):
        self.group = group
        self.user_regex = user_regex
        self.source_regex = source_regex

    def matches(self, user: str, source: str) -> bool:
        if self.user_regex and not re.fullmatch(self.user_regex, user):
            return False
        if self.source_regex and not re.fullmatch(self.source_regex,
                                                  source):
            return False
        return True


class ResourceGroupManager:
    """Owns the group forest and the selector list.  ``groups`` maps
    every node (roots and descendants) by name, so selectors can target
    nested leaves directly."""

    def __init__(self, groups: Optional[List[ResourceGroup]] = None,
                 selectors: Optional[List[Selector]] = None):
        roots = groups or [ResourceGroup("global")]
        self.roots = roots
        self.groups = {}
        for r in roots:
            for g in r.walk():
                if g.name in self.groups:
                    raise ValueError(f"duplicate group name {g.name!r}")
                self.groups[g.name] = g
        self.selectors = selectors or [Selector(roots[0].name)]

    def select(self, user: str = "", source: str = "") -> ResourceGroup:
        for s in self.selectors:
            if s.matches(user, source):
                g = self.groups[s.group]
                if g.children:
                    raise QueryQueueFull(
                        f"group {g.path} is not a leaf")
                return g
        raise QueryQueueFull(f"no resource group matches user={user!r}")

    def ensure_group(self, name: str, source_regex: Optional[str] = None,
                     **group_kwargs) -> ResourceGroup:
        """Idempotently add a leaf group as its OWN root — the
        background-tenant hook (streaming ingest, MV refresh): system
        work admits through its own named leaf instead of competing
        inside the interactive trees. A sibling root (not a child of an
        existing root) because grafting children under a configured
        leaf would silently stop it admitting (leaves only). With
        `source_regex`, a matching selector is prepended so statements
        tagged with that source route here too; first-match order keeps
        user-configured selectors from being shadowed for other
        sources."""
        g = self.groups.get(name)
        if g is None:
            g = ResourceGroup(name, **group_kwargs)
            self.roots.append(g)
            self.groups[name] = g
        if source_regex is not None and not any(
                s.group == name for s in self.selectors):
            self.selectors.insert(
                0, Selector(name, source_regex=source_regex))
        return g

    def attach_memory_pool(self, pool) -> None:
        for r in self.roots:
            r.attach_memory_pool(pool)

    def attach_cluster_reservations(self, provider) -> None:
        for r in self.roots:
            r.attach_cluster_reservations(provider)

    def evict_expired(self) -> None:
        now = time.monotonic()
        for r in self.roots:
            with r._lock:
                r._evict_expired_locked(now)

    def poke(self) -> None:
        """Re-run the scheduler on every tree (memory-quota headroom
        can appear without a release event)."""
        now = time.monotonic()
        for r in self.roots:
            with r._lock:
                r._schedule_locked(now)

    def total_queued(self) -> int:
        return sum(len(g._queue) for r in self.roots for g in r.walk())

    def total_running(self) -> int:
        return sum(r._running for r in self.roots)

    def grant_log(self) -> List[Tuple[str, Tuple[str, ...]]]:
        out: List[Tuple[str, Tuple[str, ...]]] = []
        for r in self.roots:
            out.extend(r.grant_log)
        return out

    def info(self) -> List[Tuple[str, dict]]:
        rows = [(g.path, g.snapshot())
                for r in self.roots for g in r.walk()]
        return sorted(rows)
