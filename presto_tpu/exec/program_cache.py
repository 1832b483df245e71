"""ProgramCache — the jitted programs of whoever outlives an executor,
and the capacities learned for them.

`jax.jit` caches by function object, and `Executor._lower` makes a new
closure for every lowering: a program kept by the executor alone is
traced, lowered and compiled again by every task, although plan,
capacities and input shapes are the last task's to the bit. The worker
(`server/task_manager.TpuTaskManager`) therefore owns one of these and
hands it to each task's executor; an executor built without one makes
its own, and behaves as it always has.

Everything here is keyed by value (a plan is a frozen dataclass tree),
so nothing of the task that first lowered a program is kept: not its
executor, not its pages. The plans are the executor's blanked ones
(expr/params.py): a literal whose value shapes nothing is an input of the
program and no part of a key, so one program, one capacity assignment and
one set of peaks serve every value of it."""

from __future__ import annotations

import collections
import threading
from typing import Callable, Dict, Tuple

from presto_tpu.obs.metrics import counter as _counter

_PROGRAMS = _counter(
    "presto_tpu_program_cache_total",
    "Island dispatches by whether the executor's program cache held the "
    "jitted program (hit) or this was its first lowering there (miss: "
    "Python trace, XLA lowering and a compile request follow)",
    labelnames=("result",))

#: programs (and, apart, plans with learned capacities) a cache keeps. A
#: TPC-H statement makes 2 to 13; a kept program keeps its executable
#: loaded on the device, so the bound is also one on device memory
PROGRAM_CACHE_ENTRIES = 256


class Program:
    """One jitted island, and what `_lower` returned beside its closure."""

    __slots__ = ("fn", "caps", "scans", "watch", "stats_box", "about",
                 "lock", "traced")

    def __init__(self, fn, caps, scans, watch, stats_box, about):
        self.fn = fn                  # the jax.jit object
        self.caps = caps              # the capacities it was lowered at
        self.scans = scans
        self.watch = watch
        self.stats_box = stats_box    # stats node ids, filled by the trace
        self.about = about            # the `dispatch` span's attributes
        #: the first call traces: one thread makes it, the others wait
        self.lock = threading.Lock()
        self.traced = False

    def __call__(self, pages, params=()):
        """`params`: the values of the plan's lifted literals, 0-d
        arrays the trace reads where the plan has a `Param`."""
        if self.traced:
            return self.fn(pages, params)
        with self.lock:
            out = self.fn(pages, params)
            self.traced = True
        return out


class ProgramCache:
    def __init__(self, entries: int = PROGRAM_CACHE_ENTRIES):
        self.entries = entries
        self._lock = threading.Lock()
        self.jitted: "collections.OrderedDict" = collections.OrderedDict()
        #: plan -> learned capacity assignment; executors lower from a
        #: copy and fold what they learn back in (`learn`)
        self.learned: "collections.OrderedDict" = collections.OrderedDict()
        self._peaks: Dict = {}     # plan -> {counter: largest need seen}
        #: caps-file entries as last written, by plan fingerprint
        self.saved: Dict[str, dict] = {}

    def program(self, key, caps, make: Callable[[], Program]
                ) -> Tuple[Program, bool]:
        """(the program under `key` at the capacities `caps`, whether it
        is new here). A plan over given inputs keeps ONE capacity
        variant, the last one lowered: once annealing or an overflow has
        moved a capacity, the variant before it is not asked for again,
        and a kept program keeps its executable loaded on the device.
        `make` only wraps a closure in `jax.jit`; nothing is traced
        until the program's first call."""
        with self._lock:
            program = self.jitted.get(key)
            if program is not None and program.caps == caps:
                self.jitted.move_to_end(key)
                _PROGRAMS.inc(result="hit")
                return program, False
            program = self.jitted[key] = make()
            self.jitted.move_to_end(key)
            while len(self.jitted) > self.entries:
                self.jitted.popitem(last=False)
        _PROGRAMS.inc(result="miss")
        return program, True

    def caps(self, plan, load: Callable[[object], dict]) -> dict:
        """A copy of the capacities learned for `plan`; `load` (the caps
        file) is asked once, when the plan is new here."""
        with self._lock:
            caps = self.learned.get(plan)
            if caps is not None:
                self.learned.move_to_end(plan)
                return dict(caps)
        loaded = load(plan)
        with self._lock:
            return dict(self._learned_for(plan, loaded))

    def _learned_for(self, plan, default: dict) -> dict:
        caps = self.learned.setdefault(plan, default)
        while len(self.learned) > self.entries:
            gone, _caps = self.learned.popitem(last=False)
            self._peaks.pop(gone, None)
        return caps

    def learn(self, plan, caps: dict, lowered=()) -> None:
        """Fold one execution's capacities into the shared assignment: a
        capacity is taken when it is new or larger, and goes down only
        where annealing `lowered` it. Concurrent tasks of one plan then
        cannot undo each other's growth."""
        with self._lock:
            shared = self._learned_for(plan, {})
            for k, v in caps.items():
                if k in lowered or v > shared.get(k, -1):
                    shared[k] = v

    def peak(self, plan, counter, need: int) -> int:
        """The largest `need` this counter of `plan` has reported, over
        every value its lifted literals have taken: annealing sizes a
        capacity from this, so a selective literal cannot shrink what a
        less selective one needed."""
        with self._lock:
            if plan not in self.learned:      # evicted: start over
                return need
            peaks = self._peaks.setdefault(plan, {})
            peak = peaks[counter] = max(peaks.get(counter, 0), need)
        return peak

    def clear(self) -> None:
        with self._lock:
            self.jitted.clear()
            self.learned.clear()
            self._peaks.clear()
            self.saved.clear()
