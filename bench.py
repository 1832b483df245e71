"""Benchmark: TPC-H throughput on the local accelerator, vs a measured
sqlite baseline over the IDENTICAL generated data.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "detail"}.

Headline metric: geomean rows/s over the full 22-query TPC-H suite
(scan pages resident on device), per-query median of BENCH_RUNS timed
runs after warmup; `detail` carries every query's median/rows-per-sec/
vs_baseline. Scan/agg shapes run as one fused program; join/window
plans run as per-operator islands (exec/executor.py) — the same paths a
worker uses.

Baseline: the reference publishes no absolute numbers (BASELINE.md), and
no JVM exists in this environment, so the measured proxy is sqlite3
executing the same SQL over the same rows (the test suite's correctness
oracle, standing in for H2QueryRunner). It is measured once and cached in
BASELINE_MEASURED.json (keyed by scale factor) because loading SF1 into
sqlite takes minutes; delete the file to re-measure. Roofline context: Q1
touches ~7 of 16 lineitem columns ~= 0.4 GB at SF1; at v5e HBM bandwidth
(~820 GB/s) one pass is ~0.5 ms, so wall time is dominated by how few
passes the compiled fragment makes, not FLOPs.

Execution routing (ISSUE 6): every query runs through a fallback
LADDER instead of a single pinned mode. Join-heavy plans try the
distributed device mesh FIRST (plan fragmented over N local devices,
ICI all_to_all hash exchanges with packed same-dtype collectives —
fragment-wise bounded programs, the production join path); scan/agg
shapes keep the fused whole-plan lane first; lifespan batching is the
last rung. Each detail entry records which `mode` executed
(fused / islands / dist_mesh_N / lifespan_batched_N); a query that
exhausts the ladder reports {"error": ..., "modes_tried": [...]}.

Adaptive-optimizer lane (ISSUE 9): every TPC-H entry carries a `hbo`
sub-dict — the query planned+executed twice against one shared
HistoryStore (run1 cold, run2 history-warm), recording per run the
HBO hit/miss counts, whether join reordering fired, and dynamic-filter
lifespans skipped, so the history-warm second run is visible in the
JSON.

Env knobs: BENCH_SF (default 1.0), BENCH_RUNS (5), BENCH_WARMUP (2),
BENCH_QUERIES (comma list or "all", the default), BENCH_FRAG_QUERIES
(comma list run lifespan-batched FIRST instead, default none),
BENCH_MESH_DEVICES (mesh width for the dist_mesh rung, default 4;
0/1 disables — on the host-CPU platform the child exports
XLA_FLAGS=--xla_force_host_platform_device_count before jax loads),
BENCH_QUERY_TIMEOUT (s, default 2400). Every bench process needs a
TPU: one that finds none exits non-zero and prints no result line.

TPC-DS lane (reference:
presto-benchto-benchmarks/.../benchmarks/presto/tpcds.yaml): set
BENCH_DS_QUERIES to a comma list (or "default" for a 10-query
scan/agg/join subset) to append ds_qNN entries to detail; BENCH_DS_SF
(default 0.1) scales the DS dataset. DS entries join the suite geomean
alongside the TPC-H ones.

Serving-tier lane: BENCH_SERVE=0 disables the `detail.serve` round
(event-loop front door driven by the closed-loop harness at
BENCH_SERVE_CLIENTS scales, default 200,600,1000, each scale
submitting BENCH_SERVE_STATEMENTS statements — default = the client
count — plus an aio-vs-threaded shell A/B sized by
BENCH_SERVE_AB_CLIENTS / BENCH_SERVE_AB_REQUESTS).

Data-plane lane: BENCH_DATA_PLANE=0 disables the `detail.data_plane`
round (serde encode/decode GB/s on a lineitem-shaped page, spool +
exchange drain GB/s over a multi-frame body, and q01/q06 at
BENCH_DATA_PLANE_SF — default 10 — streamed through bounded scan runs
and checked against a direct numpy oracle);
BENCH_DATA_PLANE_TIMEOUT_S (default 1800) bounds the child.
"""

import json
import os
import statistics
import sys
import time

BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BASELINE_MEASURED.json")


def _err(e) -> str:
    """Errors ride the final JSON line the driver parses — keep them
    short (a full compiler log once made the line unparseable)."""
    return f"{type(e).__name__}: {e}"[:200]


def _mesh_want() -> int:
    """Requested mesh width for the dist_mesh bench rung (0/1 off)."""
    return int(os.environ.get("BENCH_MESH_DEVICES", "4"))


def _ensure_host_devices() -> None:
    """The dist_mesh rung needs N local devices; the host-CPU platform
    only exposes them when asked BEFORE jax initializes. Harmless on a
    real accelerator (the flag affects only the host platform)."""
    want = _mesh_want()
    if want > 1 and "jax" not in sys.modules:
        cur = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in cur:
            os.environ["XLA_FLAGS"] = (
                f"{cur} --xla_force_host_platform_device_count={want}"
            ).strip()


def _mesh_ndev() -> int:
    """Usable mesh width: the request capped by what jax actually has
    (a TPU pod slice may expose fewer chips than asked)."""
    want = _mesh_want()
    if want <= 1:
        return want
    import jax
    return min(want, len(jax.devices()))


def _sqlite_db(conn):
    """Load the generated tables into sqlite once (minutes at SF1)."""
    import sqlite3

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from oracle import table_df

    db = sqlite3.connect(":memory:")
    tables = ["region", "nation", "supplier", "customer", "part",
              "partsupp", "orders", "lineitem"]
    for t in tables:
        df = table_df(conn, t)
        # DATE ints -> ISO strings for sqlite comparability
        for col in df.columns:
            if conn.table(t).types[col].name == "date":
                import datetime
                epoch = datetime.date(1970, 1, 1)
                df[col] = df[col].map(
                    lambda d: (epoch + datetime.timedelta(days=int(d))
                               ).isoformat())
        df.to_sql(t, db, index=False)
    return db


#: cap per sqlite query: index-less nested-loop joins can run for hours;
#: an interrupted query records the cap as a FLOOR (our vs_baseline then
#: understates the speedup — the honest direction)
SQLITE_QUERY_CAP_S = float(os.environ.get("BENCH_SQLITE_CAP", "900"))


def measure_sqlite_baseline(conn, sf, qids, db=None):
    """Wall time per query in sqlite3 over the same generated rows.

    Only a genuine cap interrupt records SQLITE_QUERY_CAP_S as a floor; any
    other failure (a to_sqlite mistranslation, an immediate sqlite error)
    must NOT be cached as a 900 s baseline — that would inflate vs_baseline
    in our favor. Such queries are skipped (no baseline -> vs_baseline 0,
    the honest direction)."""
    import sqlite3
    import threading

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from test_tpch_full import to_sqlite  # dialect bridge
    from tpch_queries import QUERIES

    own = db is None
    if own:
        db = _sqlite_db(conn)
    out = {}
    for qid in qids:
        sql = to_sqlite(QUERIES[qid])
        fired = threading.Event()

        def _interrupt():
            fired.set()
            db.interrupt()

        timer = threading.Timer(SQLITE_QUERY_CAP_S, _interrupt)
        timer.start()
        t0 = time.perf_counter()
        try:
            db.execute(sql).fetchall()
            out[str(qid)] = time.perf_counter() - t0
        except sqlite3.OperationalError as e:
            if fired.is_set() and "interrupt" in str(e).lower():
                out[str(qid)] = SQLITE_QUERY_CAP_S  # cap = floor
                print(f"# sqlite q{qid}: interrupted at "
                      f"{SQLITE_QUERY_CAP_S:.0f}s (baseline is a floor)",
                      file=sys.stderr)
            else:
                print(f"# sqlite q{qid}: ERROR (no baseline recorded) "
                      f"{_err(e)}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — never cache a bogus cap
            print(f"# sqlite q{qid}: ERROR (no baseline recorded) "
                  f"{_err(e)}", file=sys.stderr)
        finally:
            timer.cancel()
    if own:
        db.close()
    return out


def load_or_measure_baseline(conn, sf, qids):
    key = f"sf{sf:g}"
    data = {}
    if os.path.exists(BASELINE_FILE):
        with open(BASELINE_FILE) as f:
            data = json.load(f)
    missing = [q for q in qids
               if str(q) not in data.get(key, {}).get("sqlite_seconds", {})]
    if missing:
        # measure AND save one query at a time (single shared db load):
        # heavy sqlite joins at SF1 take many minutes each, and a
        # timeout mid-way must not discard the queries already measured
        db = _sqlite_db(conn)
        run_measured = {}       # survives a failed/raced file write
        for qid in missing:
            run_measured.update(
                measure_sqlite_baseline(conn, sf, [qid], db=db))
            if os.path.exists(BASELINE_FILE):
                with open(BASELINE_FILE) as f:
                    data = json.load(f)
            entry = data.setdefault(key, {}).setdefault(
                "sqlite_seconds", {})
            entry.update(run_measured)
            data[key]["note"] = (
                "sqlite3 :memory: wall seconds on identical generated "
                "data; measured on this machine, cached (delete file "
                "to re-measure)")
            try:
                tmp = f"{BASELINE_FILE}.{os.getpid()}.tmp"
                with open(tmp, "w") as f:
                    json.dump(data, f, indent=1, sort_keys=True)
                os.replace(tmp, BASELINE_FILE)
            except OSError:
                pass
    return data[key]["sqlite_seconds"]


#: scan/agg/join-representative TPC-DS subset for the default DS lane
DS_DEFAULT = [3, 7, 19, 42, 43, 52, 55, 96, 98, 27]


def _ds_qids():
    # a small scan/agg-shaped DS lane runs by DEFAULT so every round's
    # artifact carries a TPC-DS number; "default" widens to 10 queries,
    # "none" disables
    spec = os.environ.get("BENCH_DS_QUERIES", "3,42,52")
    if not spec or spec == "none":
        return []
    if spec == "default":
        return list(DS_DEFAULT)
    if spec == "all":        # every adapted spec query, not the subset
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tests"))
        from tpcds_queries import QUERIES as DSQ
        return sorted(DSQ)
    return [int(q) for q in spec.split(",")]


def main() -> None:
    sf = float(os.environ.get("BENCH_SF", "1.0"))
    runs = int(os.environ.get("BENCH_RUNS", "5"))
    warmup = int(os.environ.get("BENCH_WARMUP", "2"))
    spec = os.environ.get("BENCH_QUERIES", "all")
    qids = (list(range(1, 23)) if spec == "all"
            else [int(q) for q in spec.split(",") if q])
    frag_qids = {int(q) for q in os.environ.get(
        "BENCH_FRAG_QUERIES", "").split(",") if q}
    ds_one = os.environ.get("BENCH_DS_ONE")
    pq_one = os.environ.get("BENCH_PQ_ONE")
    if os.environ.get("BENCH_CHILD") != "1":
        return _main_orchestrator(sf, qids)
    _require_tpu_here()
    if os.environ.get("BENCH_LOAD_ONE"):
        return _load_child()
    if os.environ.get("BENCH_CHURN_ONE"):
        return _churn_child()
    if os.environ.get("BENCH_MV_ONE"):
        return _mv_child()
    if os.environ.get("BENCH_MEMORY_ONE"):
        return _memory_child()
    if os.environ.get("BENCH_DATA_PLANE_ONE"):
        return _data_plane_child()
    if os.environ.get("BENCH_SERVE_ONE"):
        return _serve_child()
    if os.environ.get("BENCH_CLUSTER_MESH_ONE"):
        return _cluster_mesh_child()
    if ds_one:
        return _ds_child(int(ds_one), runs, warmup)
    if pq_one:
        return _pq_child(int(pq_one), sf, runs, warmup)

    _ensure_host_devices()

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from tpch_queries import QUERIES

    from presto_tpu.connectors import TpchConnector
    from presto_tpu.exec import LocalEngine

    conn = TpchConnector(sf)
    engine = LocalEngine(conn)
    baseline = load_or_measure_baseline(conn, sf, qids)

    batched = int(os.environ.get("BENCH_LIFESPAN_BATCHES", "8"))
    detail = {}
    for qid in qids:
        _bench_ladder(conn, engine, qid, QUERIES[qid], baseline, runs,
                      warmup, detail, batched,
                      frag_first=qid in frag_qids)

    head_name, head = _headline(detail)
    print(json.dumps({
        "metric": f"tpch_{head_name}_sf{sf:g}_rows_per_sec",
        "value": head["rows_per_sec"],
        "unit": "rows/s",
        "vs_baseline": head["vs_baseline"],
        "detail": detail,
    }))


def _headline(detail):
    """Suite geomean over every query that ran (rows/s and
    vs_baseline); a single query's failure lowers coverage but cannot
    zero the report. Falls back to q01 when fewer than 3 queries
    succeeded (e.g. a smoke run)."""
    import math

    clean = {k: v for k, v in detail.items()
             if isinstance(v, dict) and "error" not in v
             and v.get("rows_per_sec", 0) > 0}
    if len(clean) >= 3:
        rps = [v["rows_per_sec"] for v in clean.values()]
        vsb = [v["vs_baseline"] for v in clean.values()
               if v.get("vs_baseline", 0) > 0]
        geo = math.exp(sum(math.log(x) for x in rps) / len(rps))
        geo_vs = (math.exp(sum(math.log(x) for x in vsb) / len(vsb))
                  if vsb else 0.0)
        return f"geomean{len(clean)}q", {
            "rows_per_sec": round(geo, 1),
            "vs_baseline": round(geo_vs, 3)}
    for pref in ("q01", "q06"):
        if pref in clean:
            return pref, clean[pref]
    if clean:
        k = sorted(clean)[0]
        return k, clean[k]
    qkeys = sorted(k for k, v in detail.items()
                   if isinstance(v, dict) and k.startswith(("q", "ds_",
                                                            "pq_")))
    k = qkeys[0] if qkeys else "none"
    return k, {"rows_per_sec": 0.0, "vs_baseline": 0.0}


def _child_env(**extra):
    """Env for a bench child: the parent's own (JAX_PLATFORMS included)
    plus the child-mode switches."""
    env = dict(os.environ)
    env.update(BENCH_CHILD="1", **extra)
    return env


def _require_tpu_here() -> None:
    """Bench numbers are device numbers: a bench process that finds no
    TPU exits non-zero instead of timing the host CPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {dev.platform} "
                 f"({dev.device_kind} x{len(jax.devices())})")


def _require_tpu() -> None:
    """The orchestrator's form of the check. It stays off JAX itself (a
    process that touched JAX holds the chip, and its per-query children
    could not), so a short-lived child asks and releases the chip."""
    import subprocess

    r = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, env=_child_env())
    if r.returncode != 0 or r.stdout.split()[-1:] != ["tpu"]:
        tail = (r.stderr.strip().splitlines() or [""])[-1][:200]
        sys.exit(f"bench: needs a TPU, found "
                 f"{r.stdout.strip() or 'no device'} {tail}")


def _run_query_child(qid, timeout_s, batched: bool, ds: bool = False):
    """One query in one subprocess; returns (detail_entry, stderr_tail)."""
    import subprocess

    if ds == "pq":
        extra = {"BENCH_PQ_ONE": str(qid), "BENCH_QUERIES": ""}
        key = f"pq_q{qid:02d}"
    elif ds:
        extra = {"BENCH_DS_ONE": str(qid), "BENCH_QUERIES": ""}
        key = f"ds_q{qid:02d}"
    else:
        extra = {"BENCH_QUERIES": str(qid)}
        key = f"q{qid:02d}"
        if batched:
            extra["BENCH_FRAG_QUERIES"] = str(qid)
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=_child_env(**extra),
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}, ""
    tail = (r.stderr.splitlines() or [""])[-1]
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    if line is None:
        return ({"error": f"no output (rc={r.returncode}) "
                          f"{tail[:120]}"[:200]}, tail)
    got = json.loads(line).get("detail", {})
    return got.get(key, {"error": "child produced no entry"}), tail


def _main_orchestrator(sf, qids) -> None:
    """Run each query in its own subprocess with a hard timeout: a
    compiler crash or a hang on one query must not take down the whole
    benchmark report (the driver consumes the final JSON line).
    Discipline (reference:
    presto-benchto-benchmarks/.../benchmarks/presto/tpch.yaml runs each
    query 6x with prewarm and records every one):

    - a run that finds no TPU exits non-zero before any query;
    - a query that fails whole-plan is retried lifespan-batched (small
      programs compile where whole-plan ones are rejected);
    - a failed query is always labeled (`error`), never an unlabeled
      0.0."""
    _require_tpu()

    # Per-query budget: warm (cached) queries run in seconds; a cold
    # island-program compile takes minutes.
    timeout_s = float(os.environ.get("BENCH_QUERY_TIMEOUT", "2400"))
    frag_qids = {int(q) for q in os.environ.get(
        "BENCH_FRAG_QUERIES", "").split(",") if q}
    detail = {}
    for qid in qids:
        entry, tail = _run_query_child(qid, timeout_s, qid in frag_qids)
        if "error" in entry and qid not in frag_qids:
            print(f"# q{qid:02d}: whole-plan failed ({entry['error']}); "
                  "retrying lifespan-batched", file=sys.stderr)
            retry, _ = _run_query_child(qid, timeout_s, batched=True)
            if "error" not in retry:
                entry = retry
        detail[f"q{qid:02d}"] = entry
        if tail:
            sys.stderr.write(tail + "\n")
    # TPC-DS lane (VERDICT r4 #10): ds_qNN entries join the geomean
    for qid in _ds_qids():
        entry, tail = _run_query_child(qid, timeout_s, batched=False,
                                       ds=True)
        detail[f"ds_q{qid:02d}"] = entry
        if tail:
            sys.stderr.write(tail + "\n")

    # parquet scan lane (VERDICT r4 #5): same TPC-H queries, data read
    # from parquet files instead of the generator (q6 by default so the
    # lakehouse scan path gets a number; "none" disables)
    pq_spec = os.environ.get("BENCH_PARQUET_QUERIES", "6")
    for qid in ([int(q) for q in pq_spec.split(",")
                 if q and q != "none"]
                if pq_spec and pq_spec != "none" else []):
        entry, tail = _run_query_child(qid, timeout_s, batched=False,
                                       ds="pq")
        detail[f"pq_q{qid:02d}"] = entry
        if tail:
            sys.stderr.write(tail + "\n")

    # admission front-door round (one JSON `admission` entry: ledger,
    # queue-wait percentiles, shed counters); BENCH_LOAD=0 disables
    if os.environ.get("BENCH_LOAD", "1") != "0":
        detail["admission"] = _run_load_child(
            float(os.environ.get("BENCH_LOAD_TIMEOUT_S", "240"))
            + 120.0)

    # elastic-membership churn round (one JSON `churn` entry: query
    # correctness under seeded join/drain/kill, membership counters);
    # BENCH_CHURN=0 disables
    if os.environ.get("BENCH_CHURN", "1") != "0":
        detail["churn"] = _run_churn_child(
            float(os.environ.get("BENCH_CHURN_TIMEOUT_S", "240"))
            + 120.0)

    # streaming-ingest + materialized-view round (one JSON `mv` entry:
    # incremental refresh cost vs full recompute over a continuously-
    # appending lineitem, plus staleness); BENCH_MV=0 disables
    if os.environ.get("BENCH_MV", "1") != "0":
        detail["mv"] = _run_mv_child(
            float(os.environ.get("BENCH_MV_TIMEOUT_S", "240"))
            + 120.0)

    # memory-arbitration round (one JSON `memory` entry: constrained-
    # budget wall vs unconstrained for the lifespan-fallback and
    # build-side-spill-join shapes, spill/revocation counters, killer
    # demo, exactness bit); BENCH_MEMORY=0 disables
    if os.environ.get("BENCH_MEMORY", "1") != "0":
        detail["memory"] = _run_memory_child(
            float(os.environ.get("BENCH_MEMORY_TIMEOUT_S", "240"))
            + 120.0)

    # serving-tier round (one JSON `serve` entry: event-loop front
    # door at 200 -> 1000 concurrent long-polling clients — p99,
    # server-side threads, keep-alive reuse — plus a shell A/B of the
    # aio loop vs the retired thread-per-connection shell). The engine
    # is a constant-time stub; BENCH_SERVE=0 disables
    if os.environ.get("BENCH_SERVE", "1") != "0":
        detail["serve"] = _run_serve_child(
            float(os.environ.get("BENCH_SERVE_TIMEOUT_S", "300"))
            + 120.0)

    # data-plane round (one JSON `data_plane` entry: serde GB/s,
    # exchange-drain GB/s, q01/q06 at SF10 through streaming scan
    # runs, oracle-exactness bit); BENCH_DATA_PLANE=0 disables
    if os.environ.get("BENCH_DATA_PLANE", "1") != "0":
        detail["data_plane"] = _run_data_plane_child(
            float(os.environ.get("BENCH_DATA_PLANE_TIMEOUT_S",
                                 "1800")) + 120.0)

    # cluster-mesh tier round (one JSON `cluster_mesh` entry: q03/q18
    # through the HTTP cluster with mesh-lowered fused execution —
    # walls plus the ICI-vs-HTTP exchange byte split);
    # BENCH_CLUSTER_MESH=0 disables
    if os.environ.get("BENCH_CLUSTER_MESH", "1") != "0":
        detail["cluster_mesh"] = _run_cluster_mesh_child(
            float(os.environ.get("BENCH_CLUSTER_MESH_TIMEOUT_S",
                                 "300")) + 120.0)

    head_name, head = _headline(detail)
    summary = {
        "metric": f"tpch_{head_name}_sf{sf:g}_rows_per_sec",
        "value": head["rows_per_sec"],
        "unit": "rows/s",
        "vs_baseline": head["vs_baseline"],
        "detail": detail,
    }
    # Regression gate: compare this run against the newest landed
    # BENCH round and self-report the verdict (advisory here; the
    # `python -m presto_tpu.obs.bench_check` CLI is the hard gate).
    # The import pulls in presto_tpu (hence jax) but initialises no
    # backend, and only after the last child has released the chip; its
    # platform and cache set-up raise if they fail.
    from presto_tpu.obs.bench_check import compare_rounds, find_rounds
    rounds = find_rounds(os.path.dirname(os.path.abspath(__file__)))
    if rounds:
        try:
            with open(rounds[-1], "r", encoding="utf-8") as f:
                landed = json.load(f)
            summary["detail"]["bench_check"] = compare_rounds(
                landed, {"parsed": summary})
        except (OSError, ValueError, KeyError, TypeError) as e:
            # a malformed landed round must not lose this run's line
            summary["detail"]["bench_check"] = {"error": _err(e)}
    print(json.dumps(summary))


def _ds_sqlite_baseline(conn, sf, qid) -> float:
    """Measured-and-cached sqlite seconds for one TPC-DS query (same
    discipline as the TPC-H lane; key ds_sf{sf})."""
    import sqlite3
    import threading

    key = f"ds_sf{sf:g}"
    data = {}
    if os.path.exists(BASELINE_FILE):
        with open(BASELINE_FILE) as f:
            data = json.load(f)
    cached = data.get(key, {}).get("sqlite_seconds", {}).get(str(qid))
    if cached is not None:
        return cached

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from test_tpcds import _TABLES, Q22_SQLITE, Q27_SQLITE, \
        SQLITE_OVERRIDES
    from test_tpch_full import _iso, to_sqlite
    from tpcds_queries import QUERIES as DSQ
    from oracle import table_df

    db = sqlite3.connect(":memory:")
    for t in _TABLES:
        df = table_df(conn, t)
        for col, typ in conn.schema(t):
            if typ.name == "date":
                df[col] = df[col].map(_iso)
        db.execute(f"create table {t} ({', '.join(df.columns)})")
        db.executemany(
            f"insert into {t} values "
            f"({', '.join('?' * len(df.columns))})",
            df.itertuples(index=False, name=None))
    db.commit()
    sql = to_sqlite({22: Q22_SQLITE, 27: Q27_SQLITE,
                     **SQLITE_OVERRIDES}.get(qid) or DSQ[qid])
    fired = threading.Event()

    def _interrupt():
        fired.set()
        db.interrupt()

    timer = threading.Timer(SQLITE_QUERY_CAP_S, _interrupt)
    timer.start()
    t0 = time.perf_counter()
    try:
        db.execute(sql).fetchall()
        took = time.perf_counter() - t0
    except sqlite3.OperationalError as e:
        if fired.is_set() and "interrupt" in str(e).lower():
            took = SQLITE_QUERY_CAP_S
        else:
            return 0.0
    except Exception:   # noqa: BLE001 — never cache a bogus cap
        return 0.0
    finally:
        timer.cancel()
        db.close()
    try:
        if os.path.exists(BASELINE_FILE):
            with open(BASELINE_FILE) as f:
                data = json.load(f)
        data.setdefault(key, {}).setdefault(
            "sqlite_seconds", {})[str(qid)] = took
        tmp = f"{BASELINE_FILE}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, BASELINE_FILE)
    except OSError:
        pass
    return took


def _ds_child(qid: int, runs: int, warmup: int) -> None:
    """One TPC-DS query timed on the production executor path."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from tpcds_queries import QUERIES as DSQ

    from presto_tpu.connectors import TpcdsConnector
    from presto_tpu.exec import LocalEngine

    ds_sf = float(os.environ.get("BENCH_DS_SF", "0.1"))
    conn = TpcdsConnector(ds_sf)
    engine = LocalEngine(conn)
    base_s = _ds_sqlite_baseline(conn, ds_sf, qid)
    detail = {}
    _bench_one(engine, qid, DSQ[qid], {str(qid): base_s}, runs,
               warmup, detail, prefix="ds_q")
    print(json.dumps({"metric": f"tpcds_q{qid}", "value": 0,
                      "unit": "rows/s", "vs_baseline": 0,
                      "detail": detail}))


def _pq_child(qid: int, sf: float, runs: int, warmup: int) -> None:
    """One TPC-H query timed on the PARQUET scan path (VERDICT r4 #5:
    a lakehouse-file scan bench entry, not the in-memory generator).
    The dataset materializes once into a cached parquet directory."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from tpch_queries import QUERIES

    from presto_tpu.connectors import TpchConnector
    from presto_tpu.connectors.parquet import (
        ParquetConnector, materialize_connector,
    )
    from presto_tpu.exec import LocalEngine

    pq_dir = os.environ.get(
        "BENCH_PARQUET_DIR", f"/tmp/presto_tpu_parquet_sf{sf:g}")
    gen = TpchConnector(sf)
    materialize_connector(
        gen, pq_dir,
        ["region", "nation", "supplier", "customer", "part",
         "partsupp", "orders", "lineitem"])
    conn = ParquetConnector(pq_dir)
    engine = LocalEngine(conn)
    baseline = load_or_measure_baseline(gen, sf, [qid])
    detail = {}
    _bench_one(engine, qid, QUERIES[qid], baseline, runs, warmup,
               detail, prefix="pq_q")
    print(json.dumps({"metric": f"tpch_parquet_q{qid}", "value": 0,
                      "unit": "rows/s", "vs_baseline": 0,
                      "detail": detail}))


def _load_child() -> None:
    """Admission front-door round: stand up a real statement server
    over a small TPC-H cluster, drive it with the closed-loop load
    harness (3 tenants at weights 2:1:1, zipfian mix), and emit the
    accepted/rejected/shed/dropped ledger plus queue-wait percentiles
    and the dispatcher's counter snapshot as one JSON line."""

    from presto_tpu.admission import (ResourceGroup,
                                      ResourceGroupManager, Selector)
    from presto_tpu.config import AdmissionConfig
    from presto_tpu.connectors import TpchConnector
    from presto_tpu.server.cluster import TpuCluster
    from presto_tpu.server.statement import StatementServer
    from presto_tpu.testing.load import LoadHarness

    statements = int(os.environ.get("BENCH_LOAD_STATEMENTS", "120"))
    clients = int(os.environ.get("BENCH_LOAD_CLIENTS", "24"))
    tenants = {"alpha": 2, "beta": 1, "gamma": 1}
    leaves = [ResourceGroup(n, hard_concurrency=4,
                            max_queued=max(statements, 64),
                            scheduling_weight=w)
              for n, w in tenants.items()]
    root = ResourceGroup("front", hard_concurrency=4, max_queued=0,
                         children=leaves)
    mgr = ResourceGroupManager(
        [root],
        [Selector(n, user_regex=n) for n in tenants]
        + [Selector("alpha")])
    cluster = TpuCluster(TpchConnector(0.01), n_workers=2,
                         resource_groups=mgr)
    srv = StatementServer(
        cluster, admission=AdmissionConfig(max_dispatch_threads=4))
    srv.start()
    try:
        harness = LoadHarness(
            srv.base, tenants, clients=clients, statements=statements,
            sql="select count(*) from nation", seed=11,
            timeout_s=float(os.environ.get("BENCH_LOAD_TIMEOUT_S",
                                           "240")))
        t0 = time.perf_counter()
        report = harness.run(dispatcher=srv.dispatcher, groups=mgr)
        wall = time.perf_counter() - t0
        out = report.to_dict()
        out["wall_s"] = round(wall, 3)
        out["statements_per_sec"] = (round(report.completed / wall, 1)
                                     if wall > 0 else 0.0)
        out["front_door"] = srv.dispatcher.snapshot()
    finally:
        srv.stop()
        cluster.stop()
    print(json.dumps({"metric": "admission_load_round", "value":
                      out["statements_per_sec"], "unit": "stmt/s",
                      "detail": {"admission": out}}))


def _serve_child() -> None:
    """Serving-tier round. Two parts:

    1. The real event-loop front door (StatementServer on
       AioHttpServer) under the closed-loop harness at increasing
       client counts (BENCH_SERVE_CLIENTS, default 200,600,1000) — a
       constant-time stub engine isolates the HTTP path: loop
       dispatch, keep-alive pooling, long-poll parks. Reports p99,
       server-side peak threads, and pooled-transport reuse per scale.
    2. A shell A/B: the same trivial App served by the aio loop and
       by the retired thread-per-connection shell, same client count —
       the thread-population contrast is the tentpole number.
    """
    import threading as _threading

    from presto_tpu.admission import (ResourceGroup,
                                      ResourceGroupManager, Selector)
    from presto_tpu.config import AdmissionConfig
    from presto_tpu.net import M_KEEPALIVE_REUSE
    from presto_tpu.server.statement import StatementServer
    from presto_tpu.testing.load import LoadHarness, percentile

    scales = [int(c) for c in os.environ.get(
        "BENCH_SERVE_CLIENTS", "200,600,1000").split(",") if c]
    stmts_env = os.environ.get("BENCH_SERVE_STATEMENTS", "")
    tenants = {"alpha": 2, "beta": 1, "gamma": 1}

    class _StubEngine:
        def execute_sql(self, sql):
            time.sleep(0.005)
            return [(1,)]

        def plan_sql(self, sql):
            raise ValueError("stub has no planner")

    rows = []
    for clients in scales:
        statements = int(stmts_env) if stmts_env else clients
        leaves = [ResourceGroup(n, hard_concurrency=32,
                                max_queued=statements + 100,
                                scheduling_weight=w)
                  for n, w in tenants.items()]
        root = ResourceGroup("front", hard_concurrency=32,
                             max_queued=0, children=leaves)
        mgr = ResourceGroupManager(
            [root],
            [Selector(n, user_regex=n) for n in tenants]
            + [Selector("alpha")])
        srv = StatementServer(
            _StubEngine(), resource_groups=mgr,
            admission=AdmissionConfig(max_dispatch_threads=8))
        srv.start()
        try:
            reuse0 = M_KEEPALIVE_REUSE.value(role="client-pool")
            t0 = time.perf_counter()
            report = LoadHarness(
                srv.base, tenants, clients=clients,
                statements=statements, seed=17,
                timeout_s=float(os.environ.get(
                    "BENCH_SERVE_TIMEOUT_S", "300"))).run()
            wall = time.perf_counter() - t0
            net = srv.httpd.stats()
            rows.append({
                "clients": clients, "statements": statements,
                "completed": report.completed,
                "dropped": report.dropped,
                "wall_s": round(wall, 3),
                "statements_per_sec":
                    round(report.completed / wall, 1) if wall else 0.0,
                "e2e_p50_s": round(report.latency()["e2e_p50_s"], 4),
                "e2e_p99_s": round(report.latency()["e2e_p99_s"], 4),
                "peak_server_threads": report.peak_server_threads,
                "keepalive_reuse":
                    int(M_KEEPALIVE_REUSE.value(role="client-pool")
                        - reuse0),
                "net": net,
            })
        finally:
            srv.stop()

    # ---- shell A/B: aio loop vs thread-per-connection ----------------
    from presto_tpu.net.aio_server import AioHttpServer, json_response
    from presto_tpu.net.threaded import ThreadedAppServer

    class _PingApp:
        def handle(self, req):
            return json_response(200, {"ok": True})

    ab_clients = int(os.environ.get("BENCH_SERVE_AB_CLIENTS", "200"))
    ab_requests = int(os.environ.get("BENCH_SERVE_AB_REQUESTS", "10"))

    def _shell_round(shell) -> dict:
        import socket as _socket
        lat, errs = [], [0]
        peak = [_threading.active_count()]
        stop = _threading.Event()

        def _sample():
            while not stop.is_set():
                peak[0] = max(peak[0], _threading.active_count())
                stop.wait(0.02)

        def _client():
            try:
                s = _socket.create_connection(
                    ("127.0.0.1", shell.port), timeout=30)
                s.settimeout(30)
                msg = b"GET /ping HTTP/1.1\r\nHost: b\r\n\r\n"
                for _ in range(ab_requests):
                    t0 = time.perf_counter()
                    s.sendall(msg)
                    buf = b""
                    while b"}" not in buf:
                        chunk = s.recv(4096)
                        if not chunk:
                            raise ConnectionError("torn")
                        buf += chunk
                    lat.append(time.perf_counter() - t0)
                s.close()
            except Exception:   # noqa: BLE001 — counted, not raised
                errs[0] += 1

        sampler = _threading.Thread(target=_sample, daemon=True)
        sampler.start()
        threads = [_threading.Thread(target=_client, daemon=True)
                   for _ in range(ab_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        wall = time.perf_counter() - t0
        stop.set()
        sampler.join(timeout=1)
        return {"impl": shell.stats()["impl"],
                "clients": ab_clients,
                "requests": ab_clients * ab_requests,
                "errors": errs[0],
                "wall_s": round(wall, 3),
                "rps": round(len(lat) / wall, 1) if wall else 0.0,
                "p50_ms": round(percentile(lat, 0.50) * 1e3, 2),
                "p99_ms": round(percentile(lat, 0.99) * 1e3, 2),
                "peak_threads": peak[0]}

    ab = {}
    for name, cls in (("aio", AioHttpServer),
                      ("threaded", ThreadedAppServer)):
        shell = cls(_PingApp(), "127.0.0.1", 0, role="bench").start()
        try:
            ab[name] = _shell_round(shell)
        finally:
            shell.shutdown()
            shell.server_close()

    out = {"scales": rows, "shell_ab": ab}
    headline = rows[-1]["statements_per_sec"] if rows else 0.0
    print(json.dumps({"metric": "serve_longpoll_round",
                      "value": headline, "unit": "stmt/s",
                      "detail": {"serve": out}}))


def _run_serve_child(timeout_s: float):
    """Run the serving-tier round in a subprocess; returns the `serve`
    detail dict (or an {"error": ...} entry)."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=_child_env(BENCH_SERVE_ONE="1", BENCH_QUERIES=""),
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    if line is None:
        tail = (r.stderr.splitlines() or [""])[-1]
        return {"error": f"no output (rc={r.returncode}) "
                         f"{tail[:120]}"[:200]}
    return json.loads(line).get("detail", {}).get(
        "serve", {"error": "child produced no serve entry"})


def _run_load_child(timeout_s: float):
    """Run the admission load round in a subprocess; returns the
    `admission` detail dict (or an {"error": ...} entry)."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=_child_env(BENCH_LOAD_ONE="1", BENCH_QUERIES=""),
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    if line is None:
        tail = (r.stderr.splitlines() or [""])[-1]
        return {"error": f"no output (rc={r.returncode}) "
                         f"{tail[:120]}"[:200]}
    return json.loads(line).get("detail", {}).get(
        "admission", {"error": "child produced no admission entry"})


def _churn_child() -> None:
    """Elastic-membership churn round: a small TPC-H cluster with a
    discovery service and `retry_policy=TASK` runs the chaos query set
    repeatedly while a seeded ChurnDriver joins, drains, and kills
    dynamic workers in the background. Emits the correctness ledger
    (rounds, failures, row mismatches vs the quiet baseline run), the
    churn schedule counters, and the coordinator's membership stats as
    one JSON line.

    BENCH_CHURN_COORD=1 raises the stakes to full control-plane chaos:
    a two-coordinator fleet over the same cluster shares one query
    journal, every query routes through the DBAPI client's rendezvous/
    failover path against the fleet, and the ChurnDriver's schedule
    gains seeded coordinator kills (coord_kill) alongside the worker
    verbs — measuring end-to-end HA, not just worker elasticity."""

    from presto_tpu.connectors import TpchConnector
    from presto_tpu.protocol.transport import TransportConfig
    from presto_tpu.server.cluster import TpuCluster
    from presto_tpu.server.discovery import DiscoveryService
    from presto_tpu.testing.churn import ChurnDriver

    seed = int(os.environ.get("BENCH_CHURN_SEED", "0"))
    rounds = int(os.environ.get("BENCH_CHURN_ROUNDS", "6"))
    queries = (
        "select count(*) from lineitem",
        "select l_returnflag, l_linestatus, count(*), sum(l_quantity) "
        "from lineitem group by l_returnflag, l_linestatus "
        "order by l_returnflag, l_linestatus",
        "select r_name, count(*) from nation, region "
        "where n_regionkey = r_regionkey group by r_name "
        "order by r_name",
    )
    coord_ha = os.environ.get("BENCH_CHURN_COORD", "0") != "0"
    chaos_tr = TransportConfig(
        retry_base_backoff_s=0.01, retry_max_backoff_s=0.2,
        retry_budget_s=5.0, breaker_failure_threshold=3,
        breaker_cooldown_s=0.3)
    disc = DiscoveryService("127.0.0.1", expiry_s=2.0).start()
    cluster = TpuCluster(
        TpchConnector(0.01), n_workers=2, discovery=disc,
        session_properties={"retry_policy": "TASK",
                            "query_max_execution_time": "120"},
        transport_config=chaos_tr)

    fleet = None
    journal_dir = None
    if coord_ha:
        import tempfile

        import presto_tpu.client as pclient
        from presto_tpu.protocol import transport as _tr
        from presto_tpu.testing.fleet import CoordinatorFleet

        # the DBAPI rides the process-global transport client; give it
        # the same chaos-tuned breaker as the cluster so a revived
        # coordinator is reachable again on the churn timescale
        _tr._DEFAULT_CLIENT = _tr.HttpClient(chaos_tr)
        journal_dir = tempfile.TemporaryDirectory()
        fleet = CoordinatorFleet(
            cluster, n=2,
            journal_path=os.path.join(journal_dir.name,
                                      "journal.jsonl")).start()
        conn = pclient.connect(fleet.bases, timeout_s=120)

        def _run(sql):
            # zero-dropped contract: clean shed / unreachable-window /
            # queue-full errors are retryable; bounded patience
            cur = conn.cursor()
            attempts = 0
            while True:
                attempts += 1
                try:
                    cur.execute(sql)
                    return [list(r) for r in cur.fetchall()]
                except (pclient.OverloadedError,
                        pclient.OperationalError):
                    if attempts >= 20:
                        raise
                    time.sleep(0.1)
                except pclient.DatabaseError as e:
                    if "QUEUE" not in str(e) or attempts >= 20:
                        raise
                    time.sleep(0.1)
    else:
        def _run(sql):
            return cluster.execute_sql(sql)

    driver = ChurnDriver(cluster, seed=seed, max_dynamic=2,
                         drain_timeout_s=30.0, coordinators=fleet)
    out = {"seed": seed, "rounds": rounds, "queries": len(queries),
           "coordinator_ha": coord_ha,
           "executed": 0, "failures": 0, "mismatches": 0}
    wall = 0.0
    intro = {}
    try:
        from presto_tpu.obs.profiler import PROFILER
        from presto_tpu.obs.wide_events import LEDGER
        LEDGER.clear()
        # quiet baseline on the static fleet = the row oracle (same
        # client path as the churn rounds so row representation
        # matches exactly)
        want = {sql: sorted(_run(sql)) for sql in queries}
        driver.start(interval_s=0.4)
        t0 = time.perf_counter()
        for _ in range(rounds):
            for sql in queries:
                try:
                    got = sorted(_run(sql))
                except Exception:
                    out["failures"] += 1
                    continue
                out["executed"] += 1
                if got != want[sql]:
                    out["mismatches"] += 1
        wall = time.perf_counter() - t0
        # wide-event ledger: exactly ONE event per cluster query
        # (baseline + churn round), summarized per query BEFORE the
        # introspection probes below append their own events
        evs = LEDGER.snapshot()
        out["wide_events"] = {
            "count": len(evs),
            "expected": (out["executed"] + out["failures"]
                         + len(queries)),
            "per_query": [
                {"query_id": e["query_id"], "state": e["state"],
                 "wall_s": e["wall_s"],
                 "result_rows": e["result_rows"],
                 "membership_epoch": e["membership"]["epoch"],
                 "stages": len(e["stages"])}
                for e in evs]}
        # introspection rides the same engine path as the bench load
        intro["tasks_by_state"] = {
            s: int(n) for s, n in cluster.execute_sql(
                "select state, count(*) from system.runtime.tasks "
                "group by state")}
        intro["nodes_by_state"] = {
            s: int(n) for s, n in cluster.execute_sql(
                "select state, count(*) from system.runtime.nodes "
                "group by state")}
        pstats = PROFILER.stats()
        intro["profiler"] = {
            "samples": pstats["samples"], "buckets": pstats["buckets"],
            "overhead": round(PROFILER.overhead_fraction(), 5)}
    finally:
        driver.close()
        if fleet is not None:
            out["ha"] = fleet.snapshot()
            fleet.close()
        cluster.stop()
        disc.stop()
        if journal_dir is not None:
            journal_dir.cleanup()
    out["wall_s"] = round(wall, 3)
    out["queries_per_sec"] = (round(out["executed"] / wall, 2)
                              if wall > 0 else 0.0)
    out["churn"] = {k: v for k, v in driver.report().items()
                    if k != "events"}
    out["membership"] = cluster.membership_snapshot()
    out["introspection"] = intro
    print(json.dumps({"metric": "elastic_churn_round",
                      "value": out["queries_per_sec"], "unit": "q/s",
                      "detail": {"churn": out}}))


def _cluster_mesh_child() -> None:
    """Cluster-mesh tier round: TPC-H q03/q18 through `TpuCluster`
    with `cluster_mesh_enabled=true` — the co-locatable plan fuses
    onto one mesh worker and its inter-stage exchanges ride ICI
    collectives — against the same queries on the plain HTTP path.
    Emits per-query walls, the ICI-vs-HTTP exchange byte split, and a
    rows-match bit between the two paths as one JSON line."""
    _ensure_host_devices()

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from tpch_queries import QUERIES

    from presto_tpu.connectors import TpchConnector
    from presto_tpu.server import mesh_tier
    from presto_tpu.server.cluster import TpuCluster

    sf = float(os.environ.get("BENCH_CLUSTER_MESH_SF", "0.01"))
    qids = [int(q) for q in os.environ.get(
        "BENCH_CLUSTER_MESH_QUERIES", "3,18").split(",") if q]
    conn = TpchConnector(sf)
    in_rows = sum(conn.table(t).num_rows
                  for t in ("customer", "orders", "lineitem"))
    cluster = TpuCluster(
        conn, n_workers=3,
        session_properties={"query_max_execution_time": "300",
                            "cluster_mesh_enabled": "true"})
    out = {"sf": sf, "queries": {}}
    total_wall = 0.0
    try:
        for qid in qids:
            sql = QUERIES[qid]
            # mesh path: warm (compile), then time; the tier metrics
            # bracket gives the bytes that moved over ICI collectives
            cluster.session_properties["cluster_mesh_enabled"] = "true"
            cluster.execute_sql(sql)
            ici0 = mesh_tier.ici_bytes_total()
            t0 = time.perf_counter()
            mesh_rows = cluster.execute_sql(sql)
            mesh_wall = time.perf_counter() - t0
            ici = int(mesh_tier.ici_bytes_total() - ici0)
            cm = dict(cluster.last_cluster_mesh or {})
            # HTTP control: identical query, tier off — its exchange
            # stats are the bytes the fusion replaced
            cluster.session_properties["cluster_mesh_enabled"] = "false"
            cluster.execute_sql(sql)
            t0 = time.perf_counter()
            http_rows = cluster.execute_sql(sql)
            http_wall = time.perf_counter() - t0
            exch = dict(cluster.last_exchange_stats or {})
            out["queries"][f"q{qid:02d}"] = {
                "mesh_wall_s": round(mesh_wall, 4),
                "http_wall_s": round(http_wall, 4),
                "result_rows": len(mesh_rows),
                # float tolerance: the two paths sum revenue in
                # different orders (associativity noise only)
                "rows_match_http": _mv_rows_match(
                    [list(r) for r in mesh_rows],
                    [list(r) for r in http_rows], rel=1e-6,
                    absol=1e-6),
                "ici_bytes": ici,
                "http_exchange_bytes": int(exch.get("bytes", 0)),
                "colocated_stages": int(cm.get("colocated_stages", 0)),
                "ndev": int(cm.get("ndev", 0)),
                "fallbacks": int(cm.get("fallbacks", 0)),
            }
            total_wall += mesh_wall
    finally:
        cluster.stop()
    qs = out["queries"].values()
    out["ici_bytes_total"] = sum(e["ici_bytes"] for e in qs)
    out["http_exchange_bytes_total"] = sum(
        e["http_exchange_bytes"] for e in qs)
    out["all_rows_match_http"] = all(e["rows_match_http"] for e in qs)
    out["wall_s"] = round(total_wall, 3)
    # input rows over the mesh-path wall: the lane throughput figure
    # bench_check compares round-over-round
    out["rows_per_sec"] = (round(in_rows * len(out["queries"])
                                 / total_wall, 1)
                           if total_wall > 0 else 0.0)
    print(json.dumps({"metric": "cluster_mesh_round",
                      "value": out["rows_per_sec"], "unit": "rows/s",
                      "detail": {"cluster_mesh": out}}))


def _run_cluster_mesh_child(timeout_s: float):
    """Run the cluster-mesh round in a subprocess; returns the
    `cluster_mesh` detail dict (or an {"error": ...} entry)."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=_child_env(BENCH_CLUSTER_MESH_ONE="1",
                           BENCH_QUERIES=""),
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    if line is None:
        tail = (r.stderr.splitlines() or [""])[-1]
        return {"error": f"no output (rc={r.returncode}) "
                         f"{tail[:120]}"[:200]}
    return json.loads(line).get("detail", {}).get(
        "cluster_mesh", {"error": "child produced no cluster_mesh "
                                  "entry"})


def _run_churn_child(timeout_s: float):
    """Run the elastic churn round in a subprocess; returns the
    `churn` detail dict (or an {"error": ...} entry)."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=_child_env(BENCH_CHURN_ONE="1", BENCH_QUERIES=""),
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    if line is None:
        tail = (r.stderr.splitlines() or [""])[-1]
        return {"error": f"no output (rc={r.returncode}) "
                         f"{tail[:120]}"[:200]}
    return json.loads(line).get("detail", {}).get(
        "churn", {"error": "child produced no churn entry"})


def _mv_rows_match(a, b, rel=1e-9, absol=1e-6) -> bool:
    """Row-set equality with float tolerance (incremental merge and
    full recompute sum in different orders — associativity noise only)."""
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if len(ra) != len(rb):
            return False
        for x, y in zip(ra, rb):
            if isinstance(x, float) or isinstance(y, float):
                if abs(float(x) - float(y)) > max(
                        absol, rel * max(abs(float(x)), abs(float(y)))):
                    return False
            elif x != y:
                return False
    return True


def _mv_child() -> None:
    """Streaming-ingest + materialized-view round: a memory-connector
    lineitem grows continuously through the coordinator's
    `POST /v1/ingest` front door (seeded StreamDriver) while two
    materialized views over the same TPC-H-style aggregate are
    refreshed each round — one incrementally (watermark delta merge),
    one forced to a full recompute (drop + recreate). Emits per-round
    delta-row and wall costs, the steady-state incremental/full ratios
    the <25% acceptance gate reads, observed staleness, and an
    exactness bit (both views must agree every round)."""

    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.exec import LocalEngine
    from presto_tpu.server.statement import StatementServer
    from presto_tpu.testing.stream import StreamDriver
    from presto_tpu.types import DOUBLE, VARCHAR

    seed = int(os.environ.get("BENCH_MV_SEED", "0"))
    seed_rows = int(os.environ.get("BENCH_MV_SEED_ROWS", "200000"))
    rounds = int(os.environ.get("BENCH_MV_ROUNDS", "5"))
    steps = int(os.environ.get("BENCH_MV_STEPS", "4"))

    flags = ("A", "N", "R")
    statuses = ("F", "O")

    def _row(rng, _ordinal):
        return (rng.choice(flags), rng.choice(statuses),
                round(rng.uniform(1.0, 50.0), 2),
                round(rng.uniform(900.0, 105000.0), 2))

    conn = MemoryConnector()
    conn.create("lineitem", [("l_returnflag", VARCHAR),
                             ("l_linestatus", VARCHAR),
                             ("l_quantity", DOUBLE),
                             ("l_extendedprice", DOUBLE)])
    import random as _random
    base_rng = _random.Random(f"{seed}:base")
    conn.append_rows("lineitem", [_row(base_rng, i)
                                  for i in range(seed_rows)])

    mv_sql = ("select l_returnflag, l_linestatus, count(*), "
              "sum(l_quantity), avg(l_extendedprice) from lineitem "
              "group by l_returnflag, l_linestatus")
    engine = LocalEngine(conn)
    srv = StatementServer(engine).start()
    driver = StreamDriver(srv.base, "lineitem", _row, seed=seed,
                          batch_min=200, batch_max=400)
    out = {"seed": seed, "seed_rows": seed_rows, "rounds": rounds,
           "per_round": [], "exact": True}
    try:
        engine.execute_sql(
            f"create materialized view bench_inc as {mv_sql}")
        engine.execute_sql("refresh materialized view bench_inc")
        mgr = engine.mv_manager

        def _stat(name):
            return next(s for s in mgr.stats() if s["name"] == name)

        for rnd in range(rounds):
            for _ in range(steps):
                driver.step()
            staleness = _stat("bench_inc")["staleness_seconds"]
            engine.execute_sql("refresh materialized view bench_inc")
            inc = _stat("bench_inc")
            # full-recompute cost of the same aggregate at the same
            # version: a fresh view's first refresh scans everything
            engine.execute_sql(
                f"create materialized view bench_full as {mv_sql}")
            engine.execute_sql("refresh materialized view bench_full")
            full = _stat("bench_full")
            if not _mv_rows_match(mgr.rows("bench_inc"),
                                  mgr.rows("bench_full")):
                out["exact"] = False
            engine.execute_sql("drop materialized view bench_full")
            out["per_round"].append({
                "round": rnd,
                "staleness_s": round(staleness, 3),
                "inc_kind": inc["last_refresh_kind"],
                "inc_delta_rows": inc["last_delta_rows"],
                "inc_wall_s": round(inc["last_refresh_duration_s"], 5),
                "full_delta_rows": full["last_delta_rows"],
                "full_wall_s": round(
                    full["last_refresh_duration_s"], 5)})
    finally:
        driver.close()
        srv.stop()
    out["ingest"] = driver.report()
    inc_rows = sum(r["inc_delta_rows"] for r in out["per_round"])
    full_rows = sum(r["full_delta_rows"] for r in out["per_round"])
    inc_wall = sum(r["inc_wall_s"] for r in out["per_round"])
    full_wall = sum(r["full_wall_s"] for r in out["per_round"])
    out["incremental_rounds"] = sum(
        1 for r in out["per_round"] if r["inc_kind"] == "incremental")
    out["rows_ratio"] = (round(inc_rows / full_rows, 4)
                         if full_rows else None)
    out["wall_ratio"] = (round(inc_wall / full_wall, 4)
                         if full_wall else None)
    # steady state = the rounds after plan/compile caches warmed (the
    # first two rounds pay one-time tracing for both refresh flavors)
    steady = out["per_round"][2:]
    s_inc_rows = sum(r["inc_delta_rows"] for r in steady)
    s_full_rows = sum(r["full_delta_rows"] for r in steady)
    s_inc_wall = sum(r["inc_wall_s"] for r in steady)
    s_full_wall = sum(r["full_wall_s"] for r in steady)
    out["steady_rows_ratio"] = (round(s_inc_rows / s_full_rows, 4)
                                if s_full_rows else None)
    out["steady_wall_ratio"] = (round(s_inc_wall / s_full_wall, 4)
                                if s_full_wall else None)
    # the acceptance gate: steady-state incremental refresh at <25% of
    # the full-recompute cost in BOTH scanned rows and wall time
    out["gate_under_25pct"] = bool(
        out["steady_rows_ratio"] is not None
        and out["steady_rows_ratio"] < 0.25
        and out["steady_wall_ratio"] is not None
        and out["steady_wall_ratio"] < 0.25)
    print(json.dumps({"metric": "mv_incremental_refresh_ratio",
                      "value": out["steady_wall_ratio"], "unit": "x",
                      "detail": {"mv": out}}))


def _run_mv_child(timeout_s: float):
    """Run the streaming-mv round in a subprocess; returns the `mv`
    detail dict (or an {"error": ...} entry)."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=_child_env(BENCH_MV_ONE="1", BENCH_QUERIES=""),
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    if line is None:
        tail = (r.stderr.splitlines() or [""])[-1]
        return {"error": f"no output (rc={r.returncode}) "
                         f"{tail[:120]}"[:200]}
    return json.loads(line).get("detail", {}).get(
        "mv", {"error": "child produced no mv entry"})


def _memory_child() -> None:
    """Memory-arbitration round: the same query is run unconstrained
    and then under a pool budget its static footprint cannot fit, so
    the engine must take a degraded-but-exact path — lifespan-batched
    fallback for the grouped aggregation, the Grace build-side spill
    join for the join-rooted shape. Emits per-lane wall costs (the
    price of surviving), spill/revocation counters proving the
    machinery actually fired, an exactness bit per lane, and a
    low-memory-killer demo (cluster budget blown -> biggest query dies
    with the EXCEEDED_MEMORY_LIMIT-class error)."""

    import math
    import shutil
    import tempfile

    from presto_tpu.config import Session
    from presto_tpu.connectors import TpchConnector
    from presto_tpu.exec import LocalEngine
    from presto_tpu.exec.memory import (
        ClusterMemoryManager, ExceededMemoryLimitError, MemoryPool,
    )

    sf = float(os.environ.get("BENCH_MEMORY_SF", "0.05"))
    conn = TpchConnector(sf)
    spill_dir = tempfile.mkdtemp(prefix="bench_memory_spill_")

    def _rows_close(got, want):
        if len(got) != len(want):
            return False
        for g, w in zip(sorted(got), sorted(want)):
            for gc, wc in zip(g, w):
                if isinstance(wc, float) or isinstance(gc, float):
                    if not math.isclose(gc, wc, rel_tol=1e-6,
                                        abs_tol=1e-9):
                        return False
                elif gc != wc:
                    return False
        return True

    #: (lane, sql, pool budget the footprint cannot fit)
    lanes = (
        ("fallback_agg",
         "select l_returnflag, l_linestatus, count(*), "
         "sum(l_quantity), sum(l_extendedprice) from lineitem "
         "group by l_returnflag, l_linestatus "
         "order by l_returnflag, l_linestatus",
         2 * 1024 * 1024),
        ("spill_join",
         "select n_name, r_name from nation, region "
         "where n_regionkey = r_regionkey order by 1, 2",
         6000),
    )
    out = {"sf": sf, "lanes": {}, "exact": True}
    try:
        for key, sql, budget in lanes:
            free_eng = LocalEngine(conn)
            free_eng.execute_sql(sql)              # compile warmup
            t0 = time.perf_counter()
            want = free_eng.execute_sql(sql)
            free_s = time.perf_counter() - t0

            pool = MemoryPool(budget)
            eng = LocalEngine(
                conn,
                session=Session({"spill_enabled": "true",
                                 "spill_path": spill_dir}),
                memory_pool=pool)
            eng.execute_sql(sql)                   # compile warmup
            t0 = time.perf_counter()
            got = eng.execute_sql(sql)
            pooled_s = time.perf_counter() - t0

            exact = _rows_close(got, want)
            out["exact"] = out["exact"] and exact
            entry = {
                "budget_bytes": budget,
                "rows": len(got),
                "wall_free_s": round(free_s, 4),
                "wall_pooled_s": round(pooled_s, 4),
                "slowdown": round(pooled_s / max(free_s, 1e-9), 2),
                "exact": exact,
                "pool": {"revocations": pool.revocations,
                         "revoked_bytes": pool.revoked_bytes,
                         "reserved_after": pool.reserved},
            }
            if eng.last_spill_join_stats is not None:
                entry["spill_join"] = eng.last_spill_join_stats
            if eng.last_memory_fallback_batches:
                entry["fallback_batches"] = \
                    eng.last_memory_fallback_batches
            out["lanes"][key] = entry

        # low-memory killer: node pool has headroom, the CLUSTER
        # budget is tiny; the bench query is the biggest over-budget
        # query and must die with the classified error
        pool = MemoryPool(1 << 40, revoke_threshold=1.0)
        mgr = ClusterMemoryManager([pool], budget_bytes=1000)
        eng = LocalEngine(conn, memory_pool=pool, cluster_memory=mgr)
        pool.reserve("bench_sentinel", 10)
        try:
            eng.execute_sql("select count(*) from region")
            out["killer"] = {"killed": False}
        except ExceededMemoryLimitError as e:
            out["killer"] = {"killed": True, "kills": mgr.kills,
                             "error": str(e)[:160]}
        finally:
            pool.free("bench_sentinel")
    finally:
        shutil.rmtree(spill_dir, ignore_errors=True)

    slowdowns = [v["slowdown"] for v in out["lanes"].values()
                 if v.get("slowdown", 0) > 0]
    geo = (math.exp(sum(math.log(s) for s in slowdowns)
                    / len(slowdowns)) if slowdowns else 0.0)
    out["constrained_slowdown_geomean"] = round(geo, 2)
    print(json.dumps({"metric": "memory_constrained_slowdown",
                      "value": out["constrained_slowdown_geomean"],
                      "unit": "x", "detail": {"memory": out}}))


def _run_memory_child(timeout_s: float):
    """Run the memory-arbitration round in a subprocess; returns the
    `memory` detail dict (or an {"error": ...} entry)."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=_child_env(BENCH_MEMORY_ONE="1", BENCH_QUERIES=""),
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    if line is None:
        tail = (r.stderr.splitlines() or [""])[-1]
        return {"error": f"no output (rc={r.returncode}) "
                         f"{tail[:120]}"[:200]}
    return json.loads(line).get("detail", {}).get(
        "memory", {"error": "child produced no memory entry"})


def _data_plane_page_blocks(n: int):
    """A lineitem-shaped wire page: 2 LONG keys, an INT line number,
    4 float64-as-LONG measures, 3 INT dates, 2 dictionary strings —
    the mixed-type shape the exchange actually ships."""
    import numpy as np

    from presto_tpu.protocol.serde import WireBlock

    rng = np.random.default_rng(11)
    blocks = [
        WireBlock("LONG_ARRAY",
                  rng.integers(0, 6_000_000, n, dtype=np.int64)),
        WireBlock("LONG_ARRAY",
                  rng.integers(0, 200_000, n, dtype=np.int64)),
        WireBlock("INT_ARRAY", rng.integers(1, 8, n, dtype=np.int32)),
    ]
    for _ in range(4):
        blocks.append(WireBlock(
            "LONG_ARRAY", rng.random(n).view(np.int64)))
    for _ in range(3):
        blocks.append(WireBlock(
            "INT_ARRAY",
            rng.integers(8000, 10600, n, dtype=np.int32)))
    d = WireBlock("VARIABLE_WIDTH",
                  np.array([b"A", b"N", b"R"], dtype=object))
    for _ in range(2):
        blocks.append(WireBlock(
            "DICTIONARY", rng.integers(0, 3, n, dtype=np.int32),
            dictionary=d))
    return blocks


def _data_plane_child() -> None:
    """Data-plane round: (1) serde encode/decode GB/s on a
    lineitem-shaped page (the zero-copy PageBuffer path), (2) spool +
    exchange drain GB/s — frames appended to a FrameFile, read back as
    memoryview ranges, every frame decoded, (3) q01/q06 at
    BENCH_DATA_PLANE_SF streamed through bounded scan runs
    (streaming_scan_rows) and checked against a direct numpy oracle
    (sqlite is infeasible at SF10)."""

    import math

    import numpy as np

    from presto_tpu.protocol.serde import (
        decode_serialized_page, encode_serialized_page,
    )

    out = {}

    # ---- serde microbench -------------------------------------------
    n = int(os.environ.get("BENCH_DATA_PLANE_ROWS", "131072"))
    reps = int(os.environ.get("BENCH_DATA_PLANE_REPS", "10"))
    blocks = _data_plane_page_blocks(n)
    frame = encode_serialized_page(blocks)
    size = len(frame)
    encode_serialized_page(blocks)                 # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        encode_serialized_page(blocks)
    enc_s = (time.perf_counter() - t0) / reps
    decode_serialized_page(frame)                  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        decode_serialized_page(frame)
    dec_s = (time.perf_counter() - t0) / reps
    out["serde"] = {"rows": n, "frame_bytes": size,
                    "encode_gbps": round(size / enc_s / 1e9, 3),
                    "decode_gbps": round(size / dec_s / 1e9, 3)}

    # ---- spool + exchange drain -------------------------------------
    from presto_tpu.spool.files import FrameFile

    nframes = int(os.environ.get("BENCH_DATA_PLANE_FRAMES", "24"))
    ff = FrameFile(prefix="bench_data_plane_")
    try:
        for _ in range(nframes):
            ff.append(frame)
        total = size * nframes
        t0 = time.perf_counter()
        token, drained, pages = 0, 0, 0
        while True:
            frames, token = ff.read_range(token, 8 << 20)
            if not frames:
                break
            for fr in frames:
                decode_serialized_page(fr)
                drained += len(fr)
                pages += 1
        drain_s = time.perf_counter() - t0
        assert drained == total and pages == nframes
        out["drain"] = {"frames": nframes, "bytes": total,
                        "drain_gbps": round(total / drain_s / 1e9, 3)}
    finally:
        ff.close()

    # ---- q01/q06 at scale, streamed, oracle-exact -------------------
    from presto_tpu.config import Session
    from presto_tpu.connectors import TpchConnector
    from presto_tpu.exec import LocalEngine
    from presto_tpu.exec.lifespan import execute_batched

    sf = float(os.environ.get("BENCH_DATA_PLANE_SF", "10"))
    run_rows = int(os.environ.get("BENCH_DATA_PLANE_RUN_ROWS",
                                  "2000000"))
    batches = int(os.environ.get("BENCH_DATA_PLANE_BATCHES", "8"))
    t0 = time.perf_counter()
    conn = TpchConnector(sf)
    t = conn.table("lineitem")
    gen_s = time.perf_counter() - t0
    nrows = int(t.num_rows)
    qty = t.arrays["l_quantity"][:nrows]
    eprice = t.arrays["l_extendedprice"][:nrows]
    disc = t.arrays["l_discount"][:nrows]
    sdate = t.arrays["l_shipdate"][:nrows]
    rf = t.arrays["l_returnflag"][:nrows]
    ls = t.arrays["l_linestatus"][:nrows]

    def close(g, w):
        return math.isclose(g, w, rel_tol=1e-6, abs_tol=1e-9)

    from presto_tpu.expr.compile import days_from_civil
    cutoff = days_from_civil(1998, 9, 2)

    # q01 oracle: grouped sums over the dictionary codes (StringDict is
    # sorted, so code order == ORDER BY 1, 2)
    keep = sdate <= cutoff
    key = rf[keep].astype(np.int64) * 64 + ls[keep]
    uniq, inv = np.unique(key, return_inverse=True)
    o_cnt = np.bincount(inv)
    o_qty = np.bincount(inv, weights=qty[keep])
    o_ep = np.bincount(inv, weights=eprice[keep])
    o_avg = np.bincount(inv, weights=disc[keep]) / o_cnt
    q01_want = [
        (t.dicts["l_returnflag"][int(k) // 64],
         t.dicts["l_linestatus"][int(k) % 64],
         o_qty[i], o_ep[i], o_avg[i], int(o_cnt[i]))
        for i, k in enumerate(uniq)]

    q06_keep = (disc >= 0.05) & (disc <= 0.07) & (qty < 24)
    q06_want = float((eprice[q06_keep] * disc[q06_keep]).sum())

    engine = LocalEngine(conn)
    session = Session({"streaming_scan_rows": str(run_rows)})
    lanes = {
        "q01": ("select l_returnflag, l_linestatus, sum(l_quantity), "
                "sum(l_extendedprice), avg(l_discount), count(*) "
                "from lineitem "
                "where l_shipdate <= date '1998-09-02' "
                "group by l_returnflag, l_linestatus order by 1, 2"),
        "q06": ("select sum(l_extendedprice * l_discount) from lineitem "
                "where l_discount between 0.05 and 0.07 "
                "and l_quantity < 24"),
    }
    out["queries"] = {"sf": sf, "lineitem_rows": nrows,
                      "gen_s": round(gen_s, 1), "batches": batches,
                      "streaming_scan_rows": run_rows, "exact": True}
    for name, sql in lanes.items():
        plan = engine.executor._resolve_subqueries(engine.plan_sql(sql))
        stats = {}
        t0 = time.perf_counter()
        page = execute_batched(conn, plan, batches, session=session,
                               stats=stats)
        wall = time.perf_counter() - t0
        got = page.to_pylist()
        if name == "q01":
            exact = len(got) == len(q01_want) and all(
                g[0] == w[0] and g[1] == w[1]
                and all(close(a, b) for a, b in zip(g[2:], w[2:]))
                for g, w in zip(got, q01_want))
        else:
            exact = close(got[0][0], q06_want)
        out["queries"]["exact"] = out["queries"]["exact"] and exact
        out["queries"][name] = {
            "wall_s": round(wall, 2), "exact": exact,
            "rows_per_sec": round(nrows / wall, 1), **stats}

    geo = math.sqrt(out["serde"]["encode_gbps"]
                    * out["serde"]["decode_gbps"])
    print(json.dumps({"metric": "data_plane_serde_gbps",
                      "value": round(geo, 3), "unit": "gb/s",
                      "detail": {"data_plane": out}}))


def _run_data_plane_child(timeout_s: float):
    """Run the data-plane round in a subprocess; returns the
    `data_plane` detail dict (or an {"error": ...} entry)."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=_child_env(BENCH_DATA_PLANE_ONE="1", BENCH_QUERIES=""),
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")), None)
    if line is None:
        tail = (r.stderr.splitlines() or [""])[-1]
        return {"error": f"no output (rc={r.returncode}) "
                         f"{tail[:120]}"[:200]}
    return json.loads(line).get("detail", {}).get(
        "data_plane", {"error": "child produced no data_plane entry"})


def _hbo_probe(conn, sql):
    """Adaptive-optimizer snapshot for one query: plan+execute it twice
    against ONE shared HistoryStore so the JSON shows the history-warm
    second run (run1 misses, run2 answers estimates from measurements).
    Each run uses a fresh engine — plan caches are per-engine, so run 2
    genuinely re-plans from history rather than reusing run 1's plan."""
    from presto_tpu.config import Session
    from presto_tpu.exec import LocalEngine
    from presto_tpu.plan.stats import HistoryStore

    hist = HistoryStore()
    out = {}
    for run in ("run1", "run2"):
        eng = LocalEngine(conn,
                          session=Session({"collect_stats": "true"}),
                          history=hist)
        h0 = (hist.hits, hist.misses)
        t0 = time.perf_counter()
        eng.execute_sql(sql)
        out[run] = {
            "wall_s": round(time.perf_counter() - t0, 4),
            "hbo_hits": hist.hits - h0[0],
            "hbo_misses": hist.misses - h0[1],
            "reorder_applied": eng.last_join_reorders,
            "df_lifespans_skipped": getattr(
                eng, "last_lifespan_stats", {}).get("skipped", 0),
        }
    out["history_entries"] = len(hist.rows)
    return out


def _plan_has_join(plan) -> bool:
    from presto_tpu.plan.nodes import JoinNode
    found = [False]

    def walk(n):
        if isinstance(n, JoinNode):
            found[0] = True
        for c in n.children():
            if c is not None and not found[0]:
                walk(c)
    walk(plan)
    return found[0]


def _bench_ladder(conn, engine, qid, sql, baseline, runs, warmup,
                  detail, batches, frag_first=False):
    """Fallback ladder: try execution modes in routing order until one
    produces a timing. Join-heavy plans route to the device mesh first
    (fragment-wise bounded programs over ICI exchanges beat both the
    whole-plan megaprogram and the lifespan-batched serial re-runs —
    BENCH_r03: q03 lifespan-batched ran at 0.455x sqlite); scan/agg
    shapes keep the fused lane first. An unbatchable plan shape is
    just a failed rung here, not a hard failure. The surviving entry
    records its `mode`; exhaustion emits modes_tried."""
    from presto_tpu.sql.parser import parse_sql

    key = f"q{qid:02d}"
    plan = engine.planner.plan_query(parse_sql(sql))
    ndev = _mesh_ndev()

    def fused():
        _bench_one(engine, qid, sql, baseline, runs, warmup, detail)

    def dist():
        _bench_one_dist(conn, qid, sql, baseline, runs, warmup, detail,
                        ndev)

    def batched_rung():
        _bench_one_batched(conn, qid, sql, baseline, runs, warmup,
                           detail, batches)

    rungs = [("fused", fused), (f"dist_mesh_{ndev}", dist),
             (f"lifespan_batched_{batches}", batched_rung)]
    if ndev <= 1:
        rungs = [r for r in rungs if not r[0].startswith("dist_mesh")]
    elif _plan_has_join(plan):
        rungs = [rungs[1], rungs[0], rungs[2]]
    if frag_first:
        rungs = sorted(rungs,
                       key=lambda r: not r[0].startswith("lifespan"))

    tried, errs = [], []
    for label, rung in rungs:
        try:
            rung()
        except Exception as e:  # noqa: BLE001 — fall to the next rung
            tried.append(label)
            errs.append(f"{label}: {_err(e)}")
            print(f"# {key}: {label} failed ({_err(e)}); "
                  "falling to next rung", file=sys.stderr)
            continue
        if tried:
            detail[key]["modes_tried"] = tried + [detail[key]["mode"]]
        # adaptive-optimizer visibility (ISSUE 9): two history-fed runs
        # per query; failure here must not fail a rung that timed fine
        try:
            detail[key]["hbo"] = _hbo_probe(conn, sql)
        except Exception as e:  # noqa: BLE001
            detail[key]["hbo"] = {"error": _err(e)}
        return
    detail[key] = {"error": "; ".join(errs)[:400], "modes_tried": tried}
    print(f"# {key}: ladder exhausted ({'; '.join(errs)[:200]})",
          file=sys.stderr)


def _bench_one_dist(conn, qid, sql, baseline, runs, warmup, detail,
                    ndev, prefix="q"):
    """Time the DISTRIBUTED path: the plan fragmented over an N-device
    local mesh (hash/range/broadcast exchanges as packed same-dtype
    all_to_all/all_gather collectives), each fragment a bounded
    shard_map program — the production join path (exec/dist_executor)."""
    import jax

    from presto_tpu.exec.dist_executor import DistEngine
    from presto_tpu.parallel import device_mesh

    dist = DistEngine(conn, device_mesh(ndev))
    ex = dist.executor
    plan = ex._prepare(ex._resolve_subqueries(dist.plan_sql(sql)))
    in_rows = sum(conn.table(t).num_rows
                  for t in sorted(_scan_tables(plan)))

    def once():
        out = ex._execute_prepared(plan)
        leaves = [c.values if hasattr(c, "values") else c.l3
                  for c in out.columns] + [out.num_rows]
        jax.block_until_ready(leaves)
        return out

    # Snapshot mesh stats from the FIRST execution: collective launches
    # and wire bytes are accounted at trace time, so warm re-dispatches
    # of cached programs report zeros.
    mesh = {}
    for i in range(max(warmup, 1)):
        once()
        if i == 0:
            mesh = dict(ex.last_mesh_stats or {})
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        once()
        times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    base_s = baseline.get(str(qid), 0.0)
    detail[f"{prefix}{qid:02d}"] = {
        "median_s": round(med, 4),
        "rows_per_sec": round(in_rows / med, 1),
        "input_rows": in_rows,
        "mode": f"dist_mesh_{ndev}",
        "mesh": {k: mesh[k] for k in
                 ("fragments", "collectives", "wire_bytes",
                  "overflow_retries") if k in mesh},
        "sqlite_baseline_s": round(base_s, 4),
        "vs_baseline": round(base_s / med, 3) if base_s else 0.0,
    }
    print(f"# {prefix}{qid:02d}: median={med:.4f}s rows={in_rows} "
          f"ndev={ndev} sqlite={base_s:.2f}s "
          f"speedup={base_s / med if base_s else 0:.1f}x",
          file=sys.stderr)


def _bench_one_batched(conn, qid, sql, baseline, runs, warmup, detail,
                       batches):
    """Lifespan-batched timing: the driving scan streams in `batches`
    row-range lifespans through ONE prepared executor (grouped-execution
    shape; reference Lifespan.java), which shrinks the per-program
    shapes by `batches`x."""
    import jax

    from presto_tpu.config import Session
    from presto_tpu.exec.lifespan import BatchedRunner
    from presto_tpu.sql.analyzer import Planner
    from presto_tpu.sql.parser import parse_sql

    plan = Planner(conn).plan_query(parse_sql(sql))
    runner = BatchedRunner(
        conn, plan, batches,
        session=Session({"dynamic_filtering_enabled": "false"}))
    if not runner.batchable:
        raise RuntimeError(f"q{qid}: plan shape is not lifespan-batchable")
    in_rows = conn.table(runner.driving).num_rows
    for _ in range(warmup):
        out = runner.run()
        jax.block_until_ready(out.num_rows)
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = runner.run()
        jax.block_until_ready((out.columns[0].values if out.columns
                               else out.num_rows, out.num_rows))
        times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    base_s = baseline.get(str(qid), 0.0)
    detail[f"q{qid:02d}"] = {
        "median_s": round(med, 4),
        "rows_per_sec": round(in_rows / med, 1),
        "input_rows": in_rows,
        "mode": f"lifespan_batched_{batches}",
        "sqlite_baseline_s": round(base_s, 4),
        "vs_baseline": round(base_s / med, 3) if base_s else 0.0,
    }
    print(f"# q{qid:02d}: median={med:.4f}s rows={in_rows} "
          f"batches={batches} sqlite={base_s:.2f}s "
          f"speedup={base_s / med if base_s else 0:.1f}x",
          file=sys.stderr)


def _bench_one(engine, qid, sql, baseline, runs, warmup, detail,
               prefix="q"):
    """Time the production execution path (Executor.execute: fused
    whole-plan programs for scan/agg shapes, per-operator islands for
    join/window plans — exactly what a worker runs). Scans come from the
    device-resident page cache, so timed runs measure compute, not
    host->device upload."""
    import jax

    from presto_tpu.sql.parser import parse_sql

    ex = engine.executor
    plan = engine.planner.plan_query(parse_sql(sql))
    plan = ex._resolve_subqueries(plan)
    plan = ex._prepare(plan)
    in_rows = sum(
        engine.connector.table(t).num_rows
        for t in sorted(_scan_tables(plan)))

    def once():
        out = ex._execute_tree(plan)
        leaves = [c.values if hasattr(c, "values") else c.l3
                  for c in out.columns] + [out.num_rows]
        jax.block_until_ready(leaves)
        return out

    for _ in range(warmup):
        once()
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        once()
        times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    base_s = baseline.get(str(qid), 0.0)
    detail[f"{prefix}{qid:02d}"] = {
        "median_s": round(med, 4),
        "rows_per_sec": round(in_rows / med, 1),
        "input_rows": in_rows,
        "mode": "islands" if ex._use_islands(plan) else "fused",
        "sqlite_baseline_s": round(base_s, 4),
        "vs_baseline": round(base_s / med, 3) if base_s else 0.0,
    }
    print(f"# {prefix}{qid:02d}: median={med:.4f}s rows={in_rows} "
          f"sqlite={base_s:.2f}s speedup={base_s/med if base_s else 0:.1f}x",
          file=sys.stderr)


def _scan_tables(plan) -> set:
    from presto_tpu.plan.nodes import TableScanNode
    out = set()

    def walk(n):
        if isinstance(n, TableScanNode):
            out.add(n.table)
        for c in n.children():
            if c is not None:
                walk(c)
    walk(plan)
    return out


if __name__ == "__main__":
    main()
