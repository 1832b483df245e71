"""From the program's own spans in a profiler trace to a layer's numbers.

The program (`presto_tpu/utils/tracing.py`) holds every span open as a
`jax.profiler.TraceAnnotation("presto:<name>", **attributes)`, so under
the profiler its spans lie in the trace's host planes, on the thread that
did the work and on the device trace's clock. This file reads them back
(`load`) and reduces them, on plain lists so that the tests feed it a
hand-made fixture (`fixtures/span_events.json`):

(a) `wall_s`: the wall a class of spans covers inside the window, the
    union over threads of its intervals;
(b) `lowering`: `dispatch` (the call of an island's jitted function) with
    the host's compile intervals taken out: what is left of a first call
    is Python tracing, lowering and the cache read;
(c) `attribute_idle`: the device's idle intervals handed out exclusively,
    in a fixed order -- `compiling`, then the LEAF spans in the order of
    LEAVES, then `unattributed_in_statement`, then `between_statements`
    -- so the classes sum to the idle time. A CONTAINER span
    (`statement`, `query`, `await_tasks`, `task_run`, `exchange_wait`,
    `device_wait`) only gives nesting and never owns idle time;
(d) `module_seconds`: device seconds by program, from the device plane's
    modules line, where the executor's programs are `jit_presto_<root
    operator>_<plan fingerprint>`; `modules_with` picks the programs whose
    `dispatch` span lists an operator.

A span that was recorded when it ended (`admission_wait`: no thread sits
in the dispatcher's queue; `exchange_pull`: whether a GET was a pull or an
empty long poll shows only in its answer) reaches the trace as a marker
carrying `waited_ms`, and is back-dated here.

    python3 benchmarks/span_reduce.py <trace dir> [--gaps N]

prints (a)-(d) for a trace directory without the run's context: the
window runs from the clock mark to the client's last round trip, a
statement from its `bench_post` to the `bench_poll` before the next, and
the compile intervals are jax's `compile_or_get_cached` frames. The
tables of PERF.md section 5 are made so.
"""

from __future__ import annotations

import bisect
import functools
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

import trace_reduce
from trace_reduce import Interval, clip, total, union

PREFIX = "presto:"
MODULES_LINE = "XLA Modules"
PROGRAM_PREFIX = "jit_presto_"

#: the spans idle time may be attributed to, in the order of attribution:
#: the coordinator's, then the tasks' work, and `exchange_pull` last --
#: a GET that lands data may have long-polled for its producer first (at
#: most `ExchangeConfig.max_wait`), so it owns only what no working span
#: covers. The consumer's wait for its producers is no leaf at all
#: (`exchange_wait`): idle time under it alone is unattributed
LEAVES = ("admission_wait", "telemetry", "plan", "schedule", "collect_root",
          "task_create", "task_plan", "deserialize", "upload", "dispatch",
          "download", "serialize", "exchange_pull")
#: the spans that only give nesting
CONTAINERS = ("statement", "query", "await_tasks", "task_run",
              "exchange_wait", "device_wait")


class Span(NamedTuple):
    name: str          # without the prefix
    thread: str        # plane and line of the trace
    start_s: float
    end_s: float
    stats: dict        # the span's attributes


def backdated(name: str, thread: str, start_s: float, duration_s: float,
              stats: dict) -> Span:
    """The span an event stands for: a marker that carries `waited_ms`
    was recorded when the wait ended, and covers the time before it."""
    waited = stats.get("waited_ms")
    if waited is not None:
        return Span(name, thread, start_s - float(waited) / 1e3, start_s,
                    stats)
    return Span(name, thread, start_s, start_s + duration_s, stats)


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """{"spans": [Span], "modules": {device plane: [Event]}, "host":
    {name: [Event]}} of one `.xplane.pb`: the program's spans, the
    executions on every device plane's modules line, and the host events
    the `__main__` needs (the client's annotations, the clock mark, jax's
    compile frames). Parsed once a process: thirteen readers share it."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    modules: Dict[str, list] = {}
    host: Dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for i, line in enumerate(plane.lines):
            if device:
                if line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events)
                continue
            thread = f"{plane.name}/{line.name}#{i}"
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    spans.append(backdated(
                        name[len(PREFIX):], thread, ev.start_ns * 1e-9,
                        ev.duration_ns * 1e-9, dict(ev.stats)))
                elif (name.startswith("bench_")
                      or name.endswith(" compile_or_get_cached")):
                    host.setdefault(name.rsplit(" ", 1)[-1], []).append(
                        (thread, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return {"spans": spans, "modules": modules, "host": host}


# ---------------------------------------------------------------- intervals

def subtract(intervals: Iterable[Interval], cover: Iterable[Interval]
             ) -> List[Interval]:
    """What `cover` leaves of `intervals` (both are merged first)."""
    out = []
    cover = union(cover)
    for lo, hi in union(intervals):
        out.extend(trace_reduce.gaps(clip(cover, lo, hi), lo, hi))
    return out


def intersect(intervals: Iterable[Interval], cover: Iterable[Interval]
              ) -> List[Interval]:
    cover = union(cover)
    return [part for lo, hi in union(intervals)
            for part in clip(cover, lo, hi)]


def covered(spans: Iterable[Span], names: Sequence[str], lo: float,
            hi: float) -> List[Interval]:
    """The union over threads of the intervals of the spans called one of
    `names`, inside [lo, hi]."""
    return union(clip(((s.start_s, s.end_s) for s in spans
                       if s.name in names), lo, hi))


def wall_s(spans: Iterable[Span], names: Sequence[str], lo: float,
           hi: float) -> float:
    return total(covered(spans, names, lo, hi))


def lowering(spans: Iterable[Span], compiling: Iterable[Interval],
             lo: float, hi: float) -> List[Interval]:
    """`dispatch` less the compile intervals."""
    return subtract(covered(spans, ("dispatch",), lo, hi), compiling)


def attribute_sum(spans: Iterable[Span], name: str, key: str, lo: float,
                  hi: float) -> float:
    """The sum of an attribute over a class of spans; a span the window
    cuts counts by the share of it inside."""
    acc = 0.0
    for s in spans:
        if s.name != name or key not in s.stats:
            continue
        inside = min(s.end_s, hi) - max(s.start_s, lo)
        length = s.end_s - s.start_s
        if length <= 0:
            acc += float(s.stats[key]) if lo <= s.start_s <= hi else 0.0
        elif inside > 0:
            acc += float(s.stats[key]) * inside / length
    return acc


# -------------------------------------------------------------- attribution

def attribute_idle(idle: Sequence[Interval], compiling: Iterable[Interval],
                   spans: Sequence[Span], in_statement: Iterable[Interval]
                   ) -> Dict[str, float]:
    """Idle seconds by owner, exclusive and in a fixed order, so that the
    classes sum to the idle time: `compiling`, each leaf span of LEAVES
    in turn, `unattributed_in_statement` (a statement was in flight and
    neither a compile nor a leaf span covers the time), and
    `between_statements`."""
    rest = union(idle)
    if not rest:
        return {}
    lo, hi = rest[0][0], rest[-1][1]
    owners = [("compiling", union(compiling))]
    owners += [(n, covered(spans, (n,), lo, hi)) for n in LEAVES]
    owners.append(("unattributed_in_statement", union(in_statement)))
    out = {}
    for name, cover in owners:
        out[name] = total(intersect(rest, cover))
        rest = subtract(rest, cover)
    out["between_statements"] = total(rest)
    return out


def gap_cover(gap: Interval, compiling: Iterable[Interval],
              spans: Sequence[Span], in_statement: Iterable[Interval]
              ) -> dict:
    """Who covers one idle gap: the exclusive owners of `attribute_idle`
    with their seconds, and, beside them, each container's seconds (not
    exclusive: containers nest)."""
    owners = attribute_idle([gap], compiling, spans, in_statement)
    nest = {c: total(covered(spans, (c,), gap[0], gap[1]))
            for c in CONTAINERS}
    return {"seconds": gap[1] - gap[0],
            "owners": {k: v for k, v in owners.items() if v > 1e-9},
            "containers": {k: v for k, v in nest.items() if v > 1e-9}}


def between_islands(spans: Sequence[Span]) -> List[Interval]:
    """Where a task is between its islands while it syncs after each:
    on every task thread, from the end of its first `device_wait` with
    `sync=per_island` to the start of its last one. The device's idle
    time in there is what the per-island sync costs: the host waits for
    island k before it traces, lowers and enqueues island k+1."""
    out = []
    for run in (s for s in spans if s.name == "task_run"):
        waits = sorted(
            (w.start_s, w.end_s) for w in spans
            if w.name == "device_wait" and w.thread == run.thread
            and w.stats.get("sync") == "per_island"
            and run.start_s <= w.start_s and w.end_s <= run.end_s)
        if len(waits) > 1:
            out.append((waits[0][1], waits[-1][0]))
    return union(out)


# ----------------------------------------------------------- device programs

def program_of(event_name: str) -> str:
    """A modules-line event's program: `jit_presto_Join_1a2b3c4d(123)` is
    an execution of `jit_presto_Join_1a2b3c4d`."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def module_seconds(events: Iterable[trace_reduce.Event], lo: float,
                   hi: float) -> Dict[str, Tuple[float, int]]:
    """{program: (device seconds, executions)} inside [lo, hi]."""
    acc: Dict[str, List[float]] = {}
    for name, start, dur in events:
        part = min(start + dur, hi) - max(start, lo)
        if part > 0:
            slot = acc.setdefault(program_of(name), [0.0, 0])
            slot[0] += part
            slot[1] += 1
    return {k: (v[0], v[1]) for k, v in acc.items()}


def ops_by_program(ops: Iterable[trace_reduce.Event],
                   modules: Iterable[trace_reduce.Event], lo: float,
                   hi: float, n: int = 12) -> List[Tuple[str, str, float]]:
    """The n operations that took most device time inside [lo, hi], each
    with the program it ran in: (program, operation, seconds). An
    operation belongs to the module execution its start lies in."""
    runs = sorted((s, s + d, program_of(name)) for name, s, d in modules)
    starts = [r[0] for r in runs]
    acc: Dict[Tuple[str, str], float] = {}
    for name, s, d in ops:
        part = min(s + d, hi) - max(s, lo)
        if part <= 0:
            continue
        i = bisect.bisect_right(starts, s) - 1
        owner = runs[i][2] if i >= 0 and s < runs[i][1] else "?"
        acc[owner, name] = acc.get((owner, name), 0.0) + part
    return [(p, o, secs) for (p, o), secs in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def modules_with(spans: Iterable[Span], operator: str,
                 without: str = "") -> set:
    """The programs whose `dispatch` span lists `operator` among its
    `operators` (joined by "+"; and not `without`)."""
    out = set()
    for s in spans:
        if s.name != "dispatch" or "program" not in s.stats:
            continue
        ops = str(s.stats.get("operators", "")).split("+")
        if operator in ops and without not in ops:
            out.add(str(s.stats["program"]))
    return out


def eager_executions(events: Iterable[trace_reduce.Event], lo: float,
                     hi: float) -> int:
    """Executions inside [lo, hi] of programs that are not the
    executor's: `jit_add`, `jit_convert_element_type`, ... run eagerly
    by planning and the host path."""
    return sum(n for name, (_s, n) in module_seconds(events, lo, hi).items()
               if not name.startswith(PROGRAM_PREFIX))


# ------------------------------------------------------------- for a reader

class Window(NamedTuple):
    """What a reader of `layer_metrics/` needs of a traced run."""
    spans: List[Span]
    modules: list        # the busiest device's modules-line events
    lo: float
    hi: float
    compiling: List[Interval]
    in_statement: List[Interval]
    statements: int
    device: list         # the busiest device's operations


def idle_of(w: Window) -> List[Interval]:
    """The idle intervals of the window's busiest device."""
    return trace_reduce.gaps(
        trace_reduce.busy_intervals(w.device, w.lo, w.hi), w.lo, w.hi)


def window(ctx: dict):
    """The Window of a run's `ctx`, or None without a trace or without a
    statement."""
    seen = ctx.get("trace")
    if seen is None or not ctx["records"]:
        return None
    data = load(seen["path"])
    busiest = seen["busiest"]
    return Window(data["spans"], data["modules"].get(busiest, []),
                  seen["lo_s"], seen["hi_s"], seen["compiling"],
                  seen["in_statement"], len(ctx["records"]),
                  seen["devices"][busiest])


def ms_per_stmt(ctx: dict, *names: str):
    """Milliseconds of wall a class of spans covers inside the window,
    over the statements attempted; None where no such span lies there."""
    w = window(ctx)
    if w is None:
        return None
    seconds = wall_s(w.spans, names, w.lo, w.hi)
    return 1e3 * seconds / w.statements if seconds > 0 else None


def device_ms_per_stmt(ctx: dict, operator: str, without: str = ""):
    """Device milliseconds inside the programs that hold `operator` (and
    not `without`), over the statements attempted."""
    w = window(ctx)
    if w is None:
        return None
    chosen = modules_with(w.spans, operator, without)
    seconds = sum(s for name, (s, _n) in module_seconds(
        w.modules, w.lo, w.hi).items() if name in chosen)
    return 1e3 * seconds / w.statements if seconds > 0 else None


# ------------------------------------------------------------------ __main__

def _standalone(trace_dir: str) -> Window:
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        raise SystemExit(f"span_reduce: no .xplane.pb under {trace_dir}")
    trace = trace_reduce.load_xplane(path)
    data = load(path)
    host = data["host"]
    trips = sorted((s, s + d, th) for name in ("bench_post", "bench_poll")
                   for th, s, d in host.get(name, []))
    posts = {(s, th) for th, s, _d in host.get("bench_post", [])}
    stmts: Dict[str, List[List[float]]] = {}
    for s, e, th in trips:
        if (s, th) in posts:
            stmts.setdefault(th, []).append([s, e])
        elif th in stmts:
            stmts[th][-1][1] = e
    in_stmt = [(a, b) for per in stmts.values() for a, b in per]
    if not in_stmt or not trace["devices"]:
        raise SystemExit("span_reduce: the trace holds no statement of "
                         "the benchmark's client, or no device plane")
    lo = trace["sync_s"] if trace["sync_s"] is not None \
        else min(a for a, _b in in_stmt)
    hi = max(b for _a, b in in_stmt)
    busy = {p: trace_reduce.busy_intervals(ev, lo, hi)
            for p, ev in trace["devices"].items()}
    busiest = max(busy, key=lambda p: total(busy[p]))
    compiling = [(s, s + d) for _th, s, d in
                 host.get("compile_or_get_cached", [])]
    return Window(data["spans"], data["modules"].get(busiest, []), lo, hi,
                  compiling, in_stmt, len(in_stmt),
                  trace["devices"][busiest])


def main(argv: Sequence[str]) -> int:
    if not argv or argv[0].startswith("-"):
        print(__doc__, file=sys.stderr)
        return 2
    n_gaps = int(argv[argv.index("--gaps") + 1]) if "--gaps" in argv else 7
    w = _standalone(argv[0])
    n, idle = w.statements, idle_of(w)
    print(f"window {w.hi - w.lo:.3f} s, {n} statements, "
          f"idle {total(idle):.3f} s, spans {len(w.spans)} "
          f"({len(w.spans) / n:.0f} a statement)")
    print("\n(a) wall covered by a span class, ms a statement")
    for name in LEAVES + CONTAINERS:
        ms = 1e3 * wall_s(w.spans, (name,), w.lo, w.hi) / n
        print(f"  {name:<18} {ms:10.1f}")
    low = total(lowering(w.spans, w.compiling, w.lo, w.hi))
    comp = total(clip(union(w.compiling), w.lo, w.hi))
    print(f"\n(b) dispatch less compile {1e3 * low / n:.1f} ms a statement; "
          f"compiling {1e3 * comp / n:.1f}")
    print("\n(c) idle seconds a statement, by owner (exclusive, in order)")
    owners = attribute_idle(idle, w.compiling, w.spans, w.in_statement)
    for name, secs in owners.items():
        print(f"  {name:<26} {secs / n:8.3f}")
    print(f"  {'sum':<26} {sum(owners.values()) / n:8.3f}  "
          f"(idle {total(idle) / n:.3f})")
    lead = total(intersect(idle, clip(between_islands(w.spans),
                                        w.lo, w.hi)))
    print(f"  idle between a task's islands (device_wait sync=per_island) "
          f"{lead / n:.3f}")
    print(f"\nthe {n_gaps} longest idle gaps")
    for g in sorted(idle, key=lambda g: g[0] - g[1])[:n_gaps]:
        c = gap_cover(g, w.compiling, w.spans, w.in_statement)
        own = ", ".join(f"{k} {v:.3f}" for k, v in sorted(
            c["owners"].items(), key=lambda kv: -kv[1]))
        nest = ", ".join(f"{k} {v:.3f}" for k, v in c["containers"].items())
        print(f"  {c['seconds']:.3f} s at +{g[0] - w.lo:.3f}: {own}  "
              f"[under {nest}]")
    print("\n(d) device seconds a statement by program (executions)")
    joins = modules_with(w.spans, "Join")
    aggs = modules_with(w.spans, "Aggregation", without="Join")
    for name, (secs, count) in sorted(
            module_seconds(w.modules, w.lo, w.hi).items(),
            key=lambda kv: -kv[1][0])[:30]:
        kind = "join" if name in joins else "agg" if name in aggs else ""
        print(f"  {name:<44} {secs / n:9.4f}  ({count / n:.1f}) {kind}")
    print("\n    the operations that took most device time, a statement")
    for prog, op, secs in ops_by_program(w.device, w.modules, w.lo, w.hi):
        print(f"  {prog:<36} {op[:60]:<60} {secs / n:8.4f}")
    print(f"  programs not the executor's: "
          f"{eager_executions(w.modules, w.lo, w.hi) / n:.1f} executions "
          "a statement")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
