"""What `q01_serial` adds to the benchmark, held where a CPU can hold it:
the cell's configuration, `tpch_sf1_http_q01`, lays out the other cells'
cluster key for key and rules the seven columns Q1 reads (a planted
fault breaks each of the three new rules); the template draws all 61
DELTAs of clause 2.4.1.3; the plain reference (groups by the pair of
words) against an independent pandas recomputation that groups by
another spelling; the float32 control comes out not correct by
`max_rel_err` alone; the new reader on the hand-made window of
`fixtures/dispatch_attr_events.json`, silent where the spans do not say;
and `test_q06.py`'s pin of the span readers, remade with the seventeen
before this PR's one. `test_control.py` and `test_faults.py` take the new
cell by themselves (they read `workloads`). Seconds, no cluster, no jit.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_q01.py -q
"""

import json
import os
import random
import sys

import numpy as np
import pandas as pd
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (HERE, BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402
import control  # noqa: E402
import datacheck  # noqa: E402
import qgen  # noqa: E402
import run as bench_run  # noqa: E402
import span_reduce  # noqa: E402

READER = "scan_aggregate_device_ms_per_stmt"
CLUSTER_KEYS = ("connector", "scale_factor", "rows", "workers", "chips",
                "session_properties", "guarantees", "reduced", "assumed")
SEVEN = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
         "l_discount", "l_tax", "l_shipdate"]


@pytest.fixture(scope="module")
def small():
    return bench_run.Tables(bench_run.make_connector("tpch", 0.01))


@pytest.fixture(scope="module")
def q01():
    query = qgen.load_query("q01")
    return query, compare.load_reference(query)


# ---- the configuration ---------------------------------------------------

def test_the_configuration_is_the_other_cells_cluster(small):
    _b, cell, mine, traffic, queries = bench_run.load_cell("q01_serial")
    assert cell["config"] == mine["name"] == "tpch_sf1_http_q01"
    assert cell["chips"] == 1 and traffic["clients"] == 1
    assert traffic["cycle"] == ["q01"] and traffic["trace_seconds"] == 20
    for other in ("q03_serial", "q18_serial", "q06_serial"):
        theirs = bench_run.load_cell(other)[2]
        for key in CLUSTER_KEYS:
            assert mine[key] == theirs[key], (other, key)
        assert mine["source"] != theirs["source"]
    assert "2.4.1 " in mine["source"] and "2.4.1.3" in mine["source"]
    assert len(mine["source"]) <= 200
    assert queries["q01"]["reads"] == {"lineitem": SEVEN}
    assert set(SEVEN) <= set(mine["tables"]["lineitem"]["columns"])
    # every rule of the Q6 configuration is kept, word for word
    q06 = qgen.load_json("configs", "tpch_sf1_http_q06.json")["tables"]
    for table in ("customer", "orders", "lineitem"):
        for col, rule in q06[table]["columns"].items():
            assert mine["tables"][table]["columns"][col] == rule
    assert mine["tables"]["lineitem"]["links"] == q06["lineitem"]["links"]
    assert datacheck.faults(small, mine, 0.01) == []


@pytest.mark.parametrize("column, fault, words", [
    ("l_tax", lambda v: np.r_[v[:-1], 0.09], None),
    ("l_tax", lambda v: np.r_[v[:-1], v[-1] + 0.004], None),
    ("l_returnflag", None, ["A", "N", "X"]),
    ("l_linestatus", None, ["F", "P"])],
    ids=["tax-over-0.08", "tax-off-the-hundredths", "flag-word",
         "status-word"])
def test_a_planted_fault_breaks_the_new_rule(small, column, fault, words):
    from test_data import Broken
    mine = qgen.load_json("configs", "tpch_sf1_http_q01.json")
    broken = Broken(
        small, column={("lineitem", column): fault} if fault else None,
        words={("lineitem", column): words} if words else None)
    found = datacheck.faults(broken, mine, 0.01)
    assert found and all(f"lineitem.{column}" in f for f in found)


def test_q01_draws_all_sixty_one_deltas(q01):
    query, _ref = q01
    seen = {qgen.statement(query, random.Random(k))[0]["DELTA"]
            for k in range(3000)}
    assert seen == set(range(60, 121))
    params, sql = qgen.statement(query, random.Random(7))
    assert f"interval '{params['DELTA']}' day" in sql and "{" not in sql


# ---- the reference -------------------------------------------------------

@pytest.mark.parametrize("delta", [60, 90, 120, 400, 1000, 1500])
def test_the_reference_agrees_with_a_pandas_recomputation(small, q01,
                                                          delta):
    """Another spelling of the same clause: the cut-off as a pandas
    timestamp, the group as the two words glued into one string, the
    sums by `groupby`. DELTA far beyond the clause's range too, where
    the cut-off drops most rows and, at 1500, two of the four groups."""
    _query, reference = q01
    li = pd.DataFrame({c: small.column("lineitem", c) for c in SEVEN})
    flags = small.words("lineitem", "l_returnflag")
    statuses = small.words("lineitem", "l_linestatus")
    ship = pd.Timestamp("1970-01-01") + pd.to_timedelta(li.l_shipdate,
                                                        unit="D")
    li = li[ship <= pd.Timestamp("1998-12-01") - pd.Timedelta(days=delta)]
    li = li.assign(
        pair=[f"{flags[int(f)]}|{statuses[int(s)]}"
              for f, s in zip(li.l_returnflag, li.l_linestatus)],
        disc_price=li.l_extendedprice * (1 - li.l_discount))
    li["charge"] = li.disc_price * (1 + li.l_tax)
    g = li.groupby("pair").agg(
        sum_qty=("l_quantity", "sum"), sum_base=("l_extendedprice", "sum"),
        sum_disc=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice",
                                                   "mean"),
        avg_disc=("l_discount", "mean"), n=("pair", "size")).sort_index()
    want = [k.split("|") + [float(v) for v in r[:7]] + [int(r[7])]
            for k, r in zip(g.index, g.itertuples(index=False))]
    got = reference(small, {"DELTA": delta})
    # by 1994-10-23 no line is open or arrives after CURRENTDATE
    assert len(got) == (2 if delta == 1500 else 4)
    gaps = compare.row_gaps(got, want)
    assert gaps["wrong_cells"] == 0 and gaps["max_rel_err"] < 1e-12
    assert sum(r[9] for r in got) == len(li)
    if delta <= 120:    # the clause's range keeps 97-99% of the rows
        n = len(small.column("lineitem", "l_shipdate"))
        assert 0.97 * n < len(li) < 0.995 * n


@pytest.mark.parametrize("seed", [3, 2**31 + 17, 123456789])
def test_float32_control_of_q01_is_not_correct(small, seed):
    _b, _c, _cfg, traffic, queries = bench_run.load_cell("q01_serial")
    verdict = control.control_run(small, traffic, queries, seed, 6)
    assert not verdict["correct"]
    failed = [k for k, s in verdict["compared"].items()
              if s["value"] > s["limit"]]
    assert failed == ["q01.max_rel_err"]
    assert verdict["compared"]["q01.wrong_cells"]["value"] == 0
    sound = control.control_run(small, traffic, queries, seed, 6,
                                dtype=np.float64)
    assert sound["correct"]
    assert all(s["value"] == 0 for s in sound["compared"].values())


# ---- the reader ----------------------------------------------------------

@pytest.fixture
def ctx(monkeypatch):
    """The hand-made window of `fixtures/dispatch_attr_events.json`: 12 s,
    three statements, nine `jit_presto_*` programs; one of them, the
    PARTIAL aggregation fused with its scan, is on the device for 3.5 s
    of the window."""
    fx = qgen.load_json("fixtures", "dispatch_attr_events.json")
    lo, hi = fx["window"]
    spans = [span_reduce.backdated(*e) for e in fx["events"]]
    monkeypatch.setattr(span_reduce, "load", lambda path: {
        "spans": spans, "host": {},
        "modules": {"/device:TPU:0": [tuple(e)
                                      for e in fx["module_events"]]}})
    return {"records": [{}] * fx["statements"], "trace": {
        "path": "fixture", "lo_s": lo, "hi_s": hi,
        "busiest": "/device:TPU:0", "devices": {"/device:TPU:0": []},
        "compiling": [], "in_statement": []}}


def _read(ctx):
    return qgen.load_py("layer_metrics", READER + ".py").read(ctx)


def test_the_reader_takes_the_programs_that_scan_and_aggregate(ctx):
    """Of the fixture's programs one lists both `TableScan` and
    `Aggregation` (the PARTIAL aggregation fused with its scan: 1.5 + 1.5
    + the 0.5 s the window keeps of its last execution); the
    aggregations over a RemoteSource or a PageInput are not taken, nor
    would a scan that only filters be."""
    spans = span_reduce.load("fixture")["spans"]
    both = {s.stats["program"] for s in spans if s.name == "dispatch"
            and {"TableScan", "Aggregation"}
            <= set(s.stats["operators"].split("+"))}
    assert both == {"jit_presto_Aggregation_11111111"}
    assert _read(ctx) == pytest.approx(1e3 * 3.5 / 3)
    spans.append(span_reduce.backdated("dispatch", "T2", 201.0, 0.25, {
        "program": "jit_presto_Join_33333333",
        "operators": "Filter+TableScan"}))
    assert _read(ctx) == pytest.approx(1e3 * 3.5 / 3)


def test_the_reader_is_silent_where_the_spans_do_not_say(ctx):
    assert _read({"records": [{}], "trace": None}) is None
    assert _read(dict(ctx, records=[])) is None
    for s in span_reduce.load("fixture")["spans"]:   # no program scans
        if "operators" in s.stats:
            s.stats["operators"] = s.stats["operators"].replace(
                "TableScan", "PageInput")
    assert _read(ctx) is None
    span_reduce.load("fixture")["spans"].clear()     # the spans are gone
    assert _read(ctx) is None


def test_the_span_readers_are_the_benchmarks_entries():
    """Every assertion of `test_spans.py::
    test_the_readers_are_the_benchmarks_entries`, of `test_q18.py::
    test_the_span_readers_are_the_benchmarks_entries` and of
    `test_q06.py`'s test of that name, each of which pins its readers as
    the *last* entries of `per_layer` and so fails on that line once
    entries follow (new entries go to the end; none of the three files is
    a cell-adding PR's to edit): PR 26's thirteen, PR 29's two and PR
    33's two are there, together and in order, with the sources and
    `workloads` lists they had, and after them comes only this PR's
    one."""
    import test_q06
    import test_q18
    import test_spans
    pinned = (list(test_spans.NEW_READERS) + list(test_q18.READERS)
              + list(test_q06.READERS))
    assert len(pinned) == 17
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert len(entries) == len(names) and set(pinned) <= set(entries)
    assert names[-18:-1] == pinned
    assert names[-1] == READER
    assert {entries[n]["source"] for n in test_spans.NEW_READERS} == {
        "program_span", "program_counter", "device_trace"}
    assert {n for n in test_spans.NEW_READERS
            if "workloads" in entries[n]} == {
        "exchange_pull_ms_per_stmt", "join_device_ms_per_stmt",
        "aggregate_device_ms_per_stmt"}
    for name in test_q18.READERS:
        assert entries[name]["workloads"] == ["q18_serial"]
        assert entries[name]["source"] == "device_trace"
    for name in test_q06.READERS:
        assert entries[name]["workloads"] == ["q06_serial"]
        assert entries[name]["source"] == "program_span"
        assert entries[name]["moves"] == "wall_p50_s"
        assert entries[name]["layer"] == "worker task: lower + compile"
    assert entries["compile_s_per_stmt"]["workloads"] == [
        "q03_serial", "q18_serial"]
    mine = entries[READER]
    assert mine == {"name": READER, "unit": "ms", "better": "lower",
                    "source": "device_trace", "layer": "device programs",
                    "moves": "wall_p50_s", "workloads": ["q01_serial"]}
    # the cell, the configuration and the entry are the last of their lists
    assert bench["workloads"][-1]["name"] == "q01_serial"
    assert bench["configs"][-1]["name"] == "tpch_sf1_http_q01"
    # `stmt_hbm_roofline` lists no cells, so it answers in the new one
    # from `queries/q01.json`'s `reads`: 44 B a row
    assert "workloads" not in entries["stmt_hbm_roofline"]
    import roofline
    assert roofline.statement_bytes(
        qgen.load_query("q01"), {"lineitem": 6001917}) == 44 * 6001917
