#!/usr/bin/env python3
"""chip_smoke.py — the served SQL path, once, on one TPU chip.

One process builds `TpchConnector(SF)`, a `TpuCluster` with its default
in-process workers and a `StatementServer`, then sends TPC-H q06, q01 and
q03 through `run_statement` (client POST /v1/statement to last row) twice
each: cold, then warm. Every result is compared, outside the timed part,
with an oracle that never touches the engine: numpy over the generated
arrays for q01/q06, a pandas merge for q03. Then q01 at both ends of
clause 2.4.1.3's DELTA (60, 120; the two statements before them ran the
validation parameter, 90) and q06 at each of clause
2.4.6.3's eight DISCOUNT values (the year and the quantity cutoff moving
with it), against the benchmark's plain reference, which decides the band
in whole hundredths: a decimal literal descaled on the device answered
five of the eight 37-50% short (PERF.md), and since the literals are
program inputs neither sweep compiles anything.

It needs a TPU: with none it exits non-zero and prints no result
(`--allow-cpu` exists only for the CPU rehearsal in the tests, `--sf` only
so that rehearsal can run at 0.01). Any mismatch or exception in any phase
is a non-zero exit. Walls printed here are a smoke's, not benchmark numbers.

The last line of stdout is the contract's:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
QIDS = (6, 1, 3)
_EPOCH = datetime.date(1970, 1, 1)


def _days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - _EPOCH).days


def _say(**fields) -> None:
    print(json.dumps(fields), flush=True)


# ---------------------------------------------------------------- oracles

def oracle_q06(conn):
    t = conn.table("lineitem")
    n = int(t.num_rows)
    qty = t.arrays["l_quantity"][:n]
    eprice = t.arrays["l_extendedprice"][:n]
    disc = t.arrays["l_discount"][:n]
    sdate = t.arrays["l_shipdate"][:n]
    keep = ((sdate >= _days(1994, 1, 1)) & (sdate < _days(1995, 1, 1))
            & (disc >= 0.05) & (disc <= 0.07) & (qty < 24))
    return [(float((eprice[keep] * disc[keep]).sum()),)]


def oracle_q01(conn):
    """Grouped sums over the dictionary codes (StringDict is sorted, so
    code order == ORDER BY 1, 2)."""
    import numpy as np
    t = conn.table("lineitem")
    n = int(t.num_rows)
    keep = t.arrays["l_shipdate"][:n] <= _days(1998, 9, 2)
    qty = t.arrays["l_quantity"][:n][keep]
    eprice = t.arrays["l_extendedprice"][:n][keep]
    disc = t.arrays["l_discount"][:n][keep]
    tax = t.arrays["l_tax"][:n][keep]
    key = (t.arrays["l_returnflag"][:n][keep].astype(np.int64) * 64
           + t.arrays["l_linestatus"][:n][keep])
    uniq, inv = np.unique(key, return_inverse=True)
    cnt = np.bincount(inv)
    disc_price = eprice * (1 - disc)
    sums = [np.bincount(inv, weights=w)
            for w in (qty, eprice, disc_price, disc_price * (1 + tax),
                      disc)]
    return [
        (t.dicts["l_returnflag"][int(k) // 64],
         t.dicts["l_linestatus"][int(k) % 64],
         sums[0][i], sums[1][i], sums[2][i], sums[3][i],
         sums[0][i] / cnt[i], sums[1][i] / cnt[i], sums[4][i] / cnt[i],
         int(cnt[i]))
        for i, k in enumerate(uniq)]


def oracle_q03(conn):
    from oracle import table_df
    cutoff = _days(1995, 3, 15)
    c = table_df(conn, "customer", ["c_custkey", "c_mktsegment"])
    o = table_df(conn, "orders", ["o_orderkey", "o_custkey",
                                  "o_orderdate", "o_shippriority"])
    li = table_df(conn, "lineitem", ["l_orderkey", "l_extendedprice",
                                     "l_discount", "l_shipdate"])
    c = c[c.c_mktsegment == "BUILDING"]
    o = o[o.o_orderdate < cutoff]
    li = li[li.l_shipdate > cutoff]
    j = (li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
         .merge(c, left_on="o_custkey", right_on="c_custkey"))
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    g = (j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                   as_index=False).revenue.sum()
         .sort_values(["revenue", "o_orderdate"], ascending=[False, True])
         .head(10))
    return [(int(r.l_orderkey), float(r.revenue), int(r.o_orderdate),
             int(r.o_shippriority)) for r in g.itertuples()]


ORACLES = {6: oracle_q06, 1: oracle_q01, 3: oracle_q03}

#: clause 2.4.6.3's DISCOUNT domain, each with a DATE and a QUANTITY
Q06_SWEEP = [{"DATE": f"{1993 + i % 5}-01-01", "DISCOUNT": f"0.0{d}",
              "QUANTITY": 24 + i % 2} for i, d in enumerate(range(2, 10))]


#: clause 2.4.1.3's DELTA at both ends (QUERIES[1] holds the 90 between)
Q01_SWEEP = [{"DELTA": 60}, {"DELTA": 120}]


def sweep(conn, base: str, counter, template: str, draws: list,
          param: str) -> dict:
    """One template at each of `draws` through the statement server, held
    to the benchmark's reference at the benchmark's limits; the
    compilations are those after the template's first statements, which
    `run_queries` sent before."""
    import compare                  # benchmarks/ is on sys.path (main)
    import qgen
    import run as bench_run
    from presto_tpu.server.statement import run_statement

    query = qgen.load_query(template)
    reference = compare.load_reference(query)
    tables = bench_run.Tables(conn)
    before = counter.compiled
    records, wanted = [], []
    for params in draws:
        _cols, rows = run_statement(base, query["sql"].format(**params))
        records.append({"template": template,
                        "rows": [list(r) for r in rows]})
        wanted.append(reference(tables, params))
    verdict = compare.judge(records, wanted, {template: query["limits"]})
    compared = verdict["compared"]
    return {"query": template + "_sweep",
            param.lower() + "s": [p[param] for p in draws],
            "exact": verdict["correct"],
            "not_exact_at": [draws[i][param]
                             for i in verdict["wrong_statements"]],
            "max_rel_err": compared[template + ".max_rel_err"]["value"],
            "wrong_cells": compared[template + ".wrong_cells"]["value"],
            "compilations": counter.compiled - before}


def rows_exact(got, want) -> str:
    """'' when the served rows equal the oracle's, in order (floats to the
    repo's own 1e-6 relative tolerance: the sums run in another order),
    else the first difference."""
    from oracle import assert_rows_match
    try:
        assert_rows_match([tuple(r) for r in got], want)
    except AssertionError as e:
        return str(e)
    return ""


# ------------------------------------------------------------------ phases

def rebuild_native_codec() -> bool:
    """Drop any libpagecodec.so left on disk (git-ignored, so it is not
    what a checkout holds) and let load() rebuild it from page_codec.cc.
    Raises when a compiler exists and the library still does not load."""
    from presto_tpu import native
    lib_path = os.path.join(os.path.dirname(native.__file__),
                            "libpagecodec.so")
    if os.path.exists(lib_path):
        os.unlink(lib_path)
    loaded = native.load() is not None
    if not loaded and shutil.which("g++"):
        raise RuntimeError("g++ exists but presto_tpu.native.load() gave "
                           "no C++ page codec")
    return loaded


def run_queries(sf: float, counter) -> bool:
    """The served path at scale `sf`; prints one line per query and one
    of run facts. Returns whether every query was exact."""
    import jax
    from tpch_queries import QUERIES

    from presto_tpu.connectors import TpchConnector
    from presto_tpu.server.cluster import TpuCluster
    from presto_tpu.server.statement import StatementServer, run_statement

    t0 = time.perf_counter()
    conn = TpchConnector(sf)
    table_rows = {t: int(conn.table(t).num_rows)
                  for t in ("lineitem", "orders", "customer")}
    generation_s = time.perf_counter() - t0

    served = {}
    cluster = TpuCluster(conn)
    try:
        srv = StatementServer(cluster).start()
        try:
            for qid in QIDS:
                entry = {}
                for phase in ("cold", "warm"):
                    before = counter.compiled, counter.seconds
                    t0 = time.perf_counter()
                    _cols, rows = run_statement(srv.base, QUERIES[qid])
                    entry[f"{phase}_s"] = time.perf_counter() - t0
                    entry[f"{phase}_compilations"] = (counter.compiled
                                                      - before[0])
                    entry[f"{phase}_compile_s"] = (counter.seconds
                                                   - before[1])
                    entry[f"{phase}_rows"] = rows
                served[qid] = entry
            sweeps = [sweep(conn, srv.base, counter, "q01", Q01_SWEEP,
                            "DELTA"),
                      sweep(conn, srv.base, counter, "q06", Q06_SWEEP,
                            "DISCOUNT")]
        finally:
            srv.stop()
    finally:
        cluster.stop()

    all_exact = True
    for qid in QIDS:
        entry = served[qid]
        want = ORACLES[qid](conn)
        diff = (rows_exact(entry["cold_rows"], want)
                or rows_exact(entry["warm_rows"], want))
        all_exact &= not diff
        line = {"query": f"q{qid:02d}", "sf": sf,
                "cold_s": entry["cold_s"], "warm_s": entry["warm_s"],
                "rows": len(entry["warm_rows"]), "exact": not diff,
                "compilations": {"cold": entry["cold_compilations"],
                                 "warm": entry["warm_compilations"]},
                "compile_thread_s": {"cold": entry["cold_compile_s"],
                                     "warm": entry["warm_compile_s"]}}
        if diff:
            line["mismatch"] = diff[:300]
        _say(**line)
    for line in sweeps:
        all_exact &= line["exact"]
        _say(sf=sf, **line)

    stats = jax.devices()[0].memory_stats() or {}
    _say(generation_s=generation_s, table_rows=table_rows,
         peak_device_bytes=stats.get("peak_bytes_in_use"),
         device_bytes_limit=stats.get("bytes_limit"),
         compile_requests=counter.requests,
         persistent_cache_hits=counter.hits)
    return all_exact


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (the CPU rehearsal uses 0.01)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="CPU rehearsal only: do not require a TPU")
    args = ap.parse_args(argv)

    for p in (REPO, os.path.join(REPO, "tests"),
              os.path.join(REPO, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    caps_existed = os.path.exists(os.path.join(REPO, ".caps_cache.json"))

    import jax

    import presto_tpu  # noqa: F401 — x64 and the compile cache

    cache_dir = jax.config.jax_compilation_cache_dir
    cache_existed = bool(cache_dir and os.path.isdir(cache_dir)
                         and os.listdir(cache_dir))
    # one listener per process (jax.monitoring has no unregister), the
    # benchmark's: two workers compile at once, so `seconds`, summed
    # over threads, can exceed the wall around them
    from benchmarks.compile_counter import compile_counter
    counter = compile_counter()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"chip_smoke: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 2

    _say(device=device, sf=args.sf,
         caps_cache_existed=caps_existed,
         compile_cache_dir=cache_dir,
         compile_cache_existed=cache_existed,
         native_codec=rebuild_native_codec())
    if not run_queries(args.sf, counter):
        print("chip_smoke: a served result differs from its oracle",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
