"""ScanCache — how many bytes of scan columns stay on the device.

`HostTable.page` keeps the device columns it builds on the table it built
them for, by (column, capacity): a table a connector holds on to — a
whole table, or a split view memoised on one (`HostTable.split`) — is
scanned again by the next statement, and what is resident is not moved
again. The columns live and die with that table instance, so a new
version of a written table (`MemoryConnector` replaces the instance) can
never read an old version's columns.

What is process-wide is only the count: this ledger knows every kept
column by size and by when it was last scanned, holds the sum under a
budget taken from the device, and drops the least recently scanned
column — from the table that keeps it — when a new one would pass it."""

from __future__ import annotations

import collections
import itertools
import threading
import weakref
from typing import Optional

from presto_tpu.obs.metrics import counter as _counter, gauge as _gauge

_SCANS = _counter(
    "presto_tpu_scan_cache_total",
    "Columns of scan pages by whether the device held them (hit: no "
    "bytes move) or they were put up for this scan (miss)",
    labelnames=("result",))
_EVICTIONS = _counter(
    "presto_tpu_scan_cache_evictions_total",
    "Resident scan columns dropped, least recently scanned first, to "
    "keep the resident bytes under the budget")
_RESIDENT = _gauge(
    "presto_tpu_scan_cache_resident_bytes",
    "Bytes of scan columns kept on the device, whole tables and split "
    "views together")

#: the share of the device's memory (`memory_stats()["bytes_limit"]`)
#: that resident scan columns may hold; the rest is the programs' own:
#: their executables, temporaries, exchanged and output pages
SCAN_CACHE_DEVICE_SHARE = 0.5
#: the budget where the backend reports no limit (the CPU)
SCAN_CACHE_BYTES = 1 << 30


def _device_budget() -> int:
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return (int(limit * SCAN_CACHE_DEVICE_SHARE) if limit
            else SCAN_CACHE_BYTES)


class KeptColumns(dict):
    """One table's device columns, by (column, capacity), and its row
    count as a page carries it."""

    __slots__ = ("serial", "num_rows", "__weakref__")
    _serials = itertools.count()

    def __init__(self):
        super().__init__()
        self.serial = next(KeptColumns._serials)
        self.num_rows = None


class ScanCache:
    def __init__(self, budget: Optional[int] = None):
        self._budget = budget
        self._lock = threading.Lock()
        #: (table's serial, (column, capacity)) -> (its KeptColumns,
        #: weakly; bytes), least recently scanned first
        self._lru: "collections.OrderedDict" = collections.OrderedDict()
        self.bytes = 0

    @property
    def budget(self) -> int:
        if self._budget is None:
            self._budget = _device_budget()
        return self._budget

    @budget.setter
    def budget(self, nbytes: Optional[int]) -> None:
        self._budget = nbytes

    def hit(self, kept: KeptColumns, key) -> None:
        """A scan found `key` resident: it is the most recent now."""
        _SCANS.inc(result="hit")
        with self._lock:
            at = (kept.serial, key)
            if at in self._lru:
                self._lru.move_to_end(at)

    def miss(self) -> None:
        _SCANS.inc(result="miss")

    def keep(self, kept: KeptColumns, key, column, nbytes: int) -> int:
        """Keep `column` under `key` of `kept` if the budget allows, at
        the cost of the least recently scanned; the bytes evicted for
        it. Two threads may put one key up at once: the later put takes
        the entry, and both columns hold the same table's rows."""
        evicted = 0
        with self._lock:
            at = (kept.serial, key)
            old = self._lru.pop(at, None)
            if old is not None:
                self.bytes -= old[1]
            # tables that died took their columns with them
            for gone in [k for k, (ref, _n) in self._lru.items()
                         if ref() is None]:
                self.bytes -= self._lru.pop(gone)[1]
            if nbytes <= self.budget:
                while self._lru and self.bytes + nbytes > self.budget:
                    (_s, lru_key), (ref, n) = self._lru.popitem(last=False)
                    self.bytes -= n
                    owner = ref()
                    if owner is not None:
                        owner.pop(lru_key, None)
                    evicted += n
                    _EVICTIONS.inc()
                kept[key] = column
                self._lru[at] = (weakref.ref(kept), nbytes)
                self.bytes += nbytes
            else:
                kept.pop(key, None)
            _RESIDENT.set(self.bytes)
        return evicted


#: the process's ledger: one device memory, one budget
SCAN_CACHE = ScanCache()
