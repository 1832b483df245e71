#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in one process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process holds the connector, the cluster with its workers, the
statement server and the client threads: a chip belongs to one process.
Set-up (import, data generation, cluster start, warm-up of every template
the cell sends) ends when the window opens; the clients then send
statements through POST /v1/statement for `--seconds`; once the last has
come back the cluster is stopped and every statement's rows are compared
with the template's plain reference. The last line of stdout is the
result object. Without a TPU (or with fewer chips than the cell asks for)
it exits non-zero and prints no result; `--allow-cpu --sf 0.01` is the
CPU rehearsal and says so in its output.

What belongs to one cell sits in files found by name: the configuration
(`configs/`), the traffic (`traffic/`), each template with its reference
(`queries/`), each per-layer metric's reader (`layer_metrics/`).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import client  # noqa: E402
import compare  # noqa: E402
import qgen  # noqa: E402
import roofline  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402

WARMUP_CAP = 4
WARMUP_TIMEOUT_S = 1100.0
TRACE_MIN_STATEMENTS = 2
# where a metric's reader lives, by its group in BENCHMARK.json
READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


class Tables:
    """What a reference may read of the generated data: the raw arrays and
    the dictionaries' words, by name."""

    def __init__(self, conn):
        self._conn = conn

    def column(self, table: str, col: str):
        t = self._conn.table(table)
        return t.arrays[col][:int(t.num_rows)]

    def words(self, table: str, col: str):
        return self._conn.table(table).dicts[col].words


def load_cell(workload: str):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    if config["chips"] != cell["chips"]:
        raise SystemExit(f"run.py: cell {workload} asks for {cell['chips']} "
                         f"chip(s), its configuration lays the cluster out "
                         f"on {config['chips']}")
    traffic = qgen.load_traffic(cell["traffic"])
    queries = {t: qgen.load_query(t) for t in dict.fromkeys(
        traffic["cycle"])}
    return bench, cell, config, traffic, queries


def metrics_of(bench: dict, group: str, workload: str):
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def warm_up(base: str, queries: dict, seed: int, counter) -> list:
    """Each template runs until two successive executions compile the same
    number of programs (cap WARMUP_CAP): the first builds the programs,
    and where learned capacities anneal, the second builds them again."""
    log = []
    for name, query in queries.items():
        counts = []
        for k in range(WARMUP_CAP):
            params, sql = qgen.statement(
                query, random.Random(f"{seed}:warmup:{name}:{k}"))
            before = counter.compiled
            rec = client.timed_statement(
                base, name, params, sql,
                time.perf_counter() + WARMUP_TIMEOUT_S)
            if not rec["ok"]:
                raise RuntimeError(f"warm-up of {name} failed: "
                                   f"{rec['error']}")
            counts.append(counter.compiled - before)
            log.append({"template": name, "compiled": counts[-1],
                        "wall_s": rec["t_done"] - rec["t_post"]})
            if len(counts) >= 2 and counts[-1] == counts[-2]:
                break
    return log


def make_connector(name: str, scale_factor: float):
    """The program's connector that calls itself `name` (its NAME), at the
    configuration's scale: a configuration over another schema names its
    connector and brings no code."""
    import presto_tpu.connectors as connectors
    for export in connectors.__all__:
        cls = getattr(connectors, export)
        if isinstance(cls, type) and getattr(cls, "NAME", None) == name:
            return cls(scale_factor)
    raise SystemExit(f"run.py: the program has no connector {name!r}")


def exchange_bytes() -> float:
    from presto_tpu.protocol.exchange import exchange_counters
    from presto_tpu.server import mesh_tier
    return exchange_counters()["bytes"] + mesh_tier.ici_bytes_total()


def device_times(trace_dir: str, chips: int, t_sync: float, t_open: float,
                 t_close: float, counter, records: list):
    """(device dict, breakdown, what a reader may take from the trace), or
    three times None, from the profiler trace of the window
    [t_open, t_close] (host clock)."""
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return None, None, None
    trace = trace_reduce.load_xplane(path)
    if not trace["devices"] or trace["sync_s"] is None:
        print(f"run.py: trace has device planes "
              f"{sorted(trace['devices'])}, clock mark "
              f"{trace['sync_s']}; lines {trace['lines']}", file=sys.stderr)
        return None, None, None
    shift = trace["sync_s"] - t_sync  # host clock -> profiler clock
    lo, hi = t_open + shift, t_close + shift
    busy = {p: trace_reduce.busy_intervals(ev, lo, hi)
            for p, ev in trace["devices"].items()}
    seconds = sorted((trace_reduce.total(b) for b in busy.values()),
                     reverse=True)[:chips]
    device = {"busy_s": sum(seconds) / chips, "busy_max_s": seconds[0],
              "window_s": hi - lo}
    busiest = max(busy, key=lambda p: trace_reduce.total(busy[p]))
    idle = trace_reduce.gaps(busy[busiest], lo, hi)
    compiling = [(a + shift, b + shift) for a, b in counter.intervals]
    in_stmt = [(r["t_post"] + shift, r["t_done"] + shift) for r in records]
    by_kind = trace_reduce.attribute_gaps(idle, compiling, in_stmt)
    breakdown = {
        "device_ops": [[name[:200], secs] for name, secs in
                       trace_reduce.top_ops(trace["devices"][busiest],
                                            lo, hi)],
        "idle_gaps": (sorted(([k, v] for k, v in by_kind.items()),
                             key=lambda kv: -kv[1])
                      + trace_reduce.longest_gaps(idle, compiling,
                                                  in_stmt, 7))}
    # for a reader of its own spans: the file, the window on the
    # profiler's clock, the shift that brings a host time onto it, the
    # device events by plane and the host's intervals already shifted
    seen = {"path": path, "dir": trace_dir, "lo_s": lo, "hi_s": hi,
            "shift_s": shift, "devices": trace["devices"],
            "busiest": busiest, "compiling": compiling,
            "in_statement": in_stmt}
    return device, breakdown, seen


def main(argv=None) -> int:
    t_start = T_PROCESS if argv is None else time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="CPU rehearsal only: do not require a TPU")
    ap.add_argument("--sf", type=float, default=None,
                    help="CPU rehearsal only: another scale factor")
    args = ap.parse_args(argv)
    bench, cell, config, traffic, queries = load_cell(args.workload)
    chips = cell["chips"]
    if (args.sf is not None) and not args.allow_cpu:
        raise SystemExit("run.py: --sf is for the --allow-cpu rehearsal")

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import jax

    import presto_tpu  # noqa: F401 -- x64 and the compile cache's place
    from compile_counter import compile_counter

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not args.allow_cpu and (not on_tpu or len(devices) < chips):
        print(f"run.py: cell {cell['name']} needs {chips} TPU chip(s), "
              f"JAX found {device}", file=sys.stderr)
        return 2
    if on_tpu:
        roofline.peaks(device["kind"])  # an unknown chip is an error
    counter = compile_counter()

    from presto_tpu.server.cluster import TpuCluster
    from presto_tpu.server.statement import StatementServer

    sf = config["scale_factor"] if args.sf is None else args.sf
    conn = make_connector(config["connector"], sf)
    tables = Tables(conn)
    for query in queries.values():  # generation is set-up, not window
        for table in query["reads"]:
            conn.table(table)
    t_generated = time.perf_counter()

    tracing = bool(args.trace)
    trace_dir = os.path.join(HERE, ".trace", cell["name"])
    seconds = (min(args.seconds, traffic["trace_seconds"]) if tracing
               else args.seconds)
    annotate = jax.profiler.TraceAnnotation if tracing else None
    cluster = TpuCluster(conn, n_workers=config["workers"],
                         session_properties=(config["session_properties"]
                                             or None))
    t_sync = None
    try:
        srv = StatementServer(cluster).start()
        try:
            warm = warm_up(srv.base, queries, args.seed, counter)
            if tracing:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
                with jax.profiler.TraceAnnotation(trace_reduce.SYNC_MARK):
                    t_sync = time.perf_counter()
            streams = [qgen.client_stream(traffic, queries, args.seed, i)
                       for i in range(traffic["clients"])]
            before = counter.snapshot(), exchange_bytes()
            setup_s = time.perf_counter() - t_start
            t_open, records = client.closed_loop(
                srv.base, streams, seconds, annotate=annotate,
                min_statements=TRACE_MIN_STATEMENTS if tracing else 0)
            window_s = stats.window_length(
                t_open, [r["t_done"] for r in records], seconds)
            t_close = t_open + window_s
            after = counter.snapshot(), exchange_bytes()
            if tracing:
                jax.profiler.stop_trace()
        finally:
            srv.stop()
    finally:
        cluster.stop()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:chips])

    # ---- the window has closed: references, then the numbers
    done = [r for r in records if r["ok"]]
    refs, wanted = {}, []
    t_ref = time.perf_counter()
    for r in done:
        key = (r["template"], json.dumps(r["params"], sort_keys=True))
        if key not in refs:
            refs[key] = compare.load_reference(queries[r["template"]])(
                tables, r["params"])
        wanted.append(refs[key])
    verdict = compare.judge(
        done, wanted, {t: q["limits"] for t, q in queries.items()})
    reference_s = time.perf_counter() - t_ref
    n_right = len(done) - len(verdict["wrong_statements"])

    dev = breakdown = seen = floor_s = None
    if tracing:
        dev, breakdown, seen = device_times(
            trace_dir, chips, t_sync, t_open, t_close, counter, records)
        if dev is not None:
            rows = config["rows"]
            floor_s = sum(roofline.hbm_floor_s(
                roofline.statement_bytes(queries[r["template"]], rows),
                device["kind"], chips) for r in records)
            device["busy_s"] = dev["busy_s"]
            device["window_s"] = dev["window_s"]
    # what a metric's reader may read; `device` and `trace` are None in a
    # run without a trace, and in a traced run whose trace held nothing
    ctx = {"records": records, "statements_right": n_right,
           "window_s": window_s, "setup_s": setup_s, "chips": chips,
           "t_open": t_open, "t_close": t_close,
           "cell": cell, "config": config, "queries": queries,
           "compile": {k: after[0][k] - before[0][k] for k in after[0]},
           "exchange_bytes": after[1] - before[1], "device": dev,
           "trace": seen, "floor_s": floor_s, "memory_peak_bytes": peak}
    group = "per_layer" if tracing else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, group, cell["name"]):
        value = qgen.load_py(READERS[group], m["name"] + ".py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device["memory_peak_bytes"] = peak
    result = {"correct": verdict["correct"], "attempted": len(records),
              "failed": len(records) - len(done), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["run"] = {
        "workload": cell["name"], "seed": args.seed, "sf": sf,
        "rehearsal_on_cpu": not on_tpu, "window_s": window_s,
        "statements_right": n_right, "setup_s": setup_s,
        "generation_s": t_generated - t_start, "warm_up": warm,
        "reference_s": reference_s,
        "compiled_in_window": after[0]["compiled"] - before[0]["compiled"],
        "errors": [r["error"] for r in records if not r["ok"]][:3],
        "wrong": [{"template": done[i]["template"],
                   "params": done[i]["params"],
                   "got": done[i]["rows"][:2], "want": wanted[i][:2]}
                  for i in verdict["wrong_statements"][:4]]}
    result["compared"] = verdict["compared"]
    for name, slot in verdict["compared"].items():
        print(f"compared {name} = {slot['value']!r} (limit "
              f"{slot['limit']!r})", file=sys.stderr)
    print(f"correct = {verdict['correct']}; {len(done)} of {len(records)} "
          f"statements came back, {n_right} right", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
