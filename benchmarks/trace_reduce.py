"""From a profiler trace to device busy time, top operations and idle gaps.

The reduction works on plain lists of (name, start_s, duration_s), so the
tests feed it a hand-made list (`fixtures/`). `load_xplane` turns the
profiler's `.xplane.pb` into those lists with nothing but JAX's reader.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # name, start_s, duration_s

SYNC_MARK = "bench_clock_sync"
# lines of a device plane that hold executed operations; the others repeat
# them by module or step, and would only be counted twice in a top list
OP_LINES = ("XLA Ops",)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted((lo, hi) for lo, hi in intervals if hi > lo):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def busy_intervals(events: Iterable[Event], lo: float, hi: float
                   ) -> List[Interval]:
    """Union of the intervals in which an operation ran, inside [lo, hi]."""
    return union(clip(((s, s + d) for _n, s, d in events), lo, hi))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] that `busy` (merged) leaves."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(gap: Interval, cover: Sequence[Interval]) -> float:
    return total(clip(cover, gap[0], gap[1]))


def attribute_gaps(idle: Sequence[Interval],
                   compiling: Iterable[Interval],
                   in_statement: Iterable[Interval]) -> Dict[str, float]:
    """Idle seconds by what the host was doing: compiling (a backend
    compile ran on some thread), in a statement but not compiling (plan,
    schedule, upload, serde, polls: not split until the program has
    spans), or between statements (no statement in flight)."""
    comp = union(compiling)
    either = union(list(comp) + list(in_statement))
    out = {"compiling": 0.0, "in_statement_not_compiling": 0.0,
           "between_statements": 0.0}
    for g in idle:
        c = overlap(g, comp)
        s = overlap(g, either)
        out["compiling"] += c
        out["in_statement_not_compiling"] += s - c
        out["between_statements"] += (g[1] - g[0]) - s
    return out


def top_ops(events: Iterable[Event], lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """The operations that took most device time inside [lo, hi]."""
    acc: Dict[str, float] = {}
    for name, s, d in events:
        part = min(s + d, hi) - max(s, lo)
        if part > 0:
            acc[name] = acc.get(name, 0.0) + part
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def longest_gaps(idle: Sequence[Interval], compiling: Iterable[Interval],
                 in_statement: Iterable[Interval], n: int = 10) -> List[List]:
    """The n longest idle gaps, each named by what covered most of it."""
    comp, stmt = union(compiling), union(in_statement)
    out = []
    for g in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        one = attribute_gaps([g], comp, stmt)
        out.append([max(one, key=one.get), g[1] - g[0]])
    return out


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def named_events(path: str, prefix: str) -> Dict[str, List[Event]]:
    """{plane name: [Event]} of every event, on any plane and line, whose
    name starts with `prefix`: a host annotation (the client's
    `bench_post`/`bench_poll`, a span the program opens with
    `jax.profiler.TraceAnnotation`) or a device operation under a
    `named_scope`. For a reader of its own spans; times are seconds on the
    profiler's clock, as in `load_xplane`."""
    from jax.profiler import ProfileData

    out: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            found = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                     for ev in line.events if ev.name.startswith(prefix)]
            if found:
                out.setdefault(plane.name, []).extend(found)
    return out


def load_xplane(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """{"devices": {plane name: [Event]}, "sync_s": start of the
    SYNC_MARK host annotation or None, "lines": {plane: [line names]}}.
    Times are seconds on the profiler's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    lines: Dict[str, List[str]] = {}
    sync = None
    for plane in data.planes:
        names = []
        is_device = plane.name.startswith(device_prefix)
        for line in plane.lines:
            names.append(line.name)
            if is_device:
                if line.name in OP_LINES:
                    devices.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events)
            elif sync is None:
                for ev in line.events:
                    if ev.name == SYNC_MARK:
                        sync = ev.start_ns * 1e-9
                        break
        lines[plane.name] = names
    return {"devices": devices, "sync_s": sync, "lines": lines}
