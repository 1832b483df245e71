"""What `q06_serial` adds to the benchmark, held where a CPU can hold it:
the cell's configuration, `tpch_sf1_http_q06`, lays out the other cells'
cluster key for key and rules the four columns Q6 reads; the plain
reference (the discount band in whole hundredths) against an independent
pandas recomputation that spells the band another way; the float32
control comes out not correct by `max_rel_err` alone; two faults planted
under the timed path (the band's upper edge dropped; the first
statement's DISCOUNT kept for every later one, as a program that bakes
its literals would) each turn a whole CPU run's `correct` false; the two
new span readers on hand-made spans, silent where the spans lack the
attribute; and `test_q18.py`'s pin of the span readers, remade with the
fifteen before what came since. The whole runs start a cluster: a
quarter of a minute a case, no tier-1 test.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_q06.py -q
"""

import datetime
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
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402
import control  # noqa: E402
import datacheck  # noqa: E402
import qgen  # noqa: E402
import run as bench_run  # noqa: E402
import span_reduce  # noqa: E402

READERS = ("program_misses_per_stmt", "literal_inputs_per_stmt")
CLUSTER_KEYS = ("connector", "scale_factor", "rows", "workers", "chips",
                "session_properties", "guarantees", "reduced", "assumed")


@pytest.fixture(scope="module")
def small():
    return bench_run.Tables(bench_run.make_connector("tpch", 0.01))


@pytest.fixture(scope="module")
def q06():
    query = qgen.load_query("q06")
    return query, compare.load_reference(query)


# ---- the configuration ---------------------------------------------------

def test_the_configuration_is_the_other_cells_cluster(small):
    _b, cell, mine, traffic, queries = bench_run.load_cell("q06_serial")
    assert cell["config"] == mine["name"] == "tpch_sf1_http_q06"
    assert cell["chips"] == 1 and traffic["clients"] == 1
    assert traffic["cycle"] == ["q06"] and traffic["trace_seconds"] == 20
    for other in ("q03_serial", "q18_serial"):
        theirs = bench_run.load_cell(other)[2]
        for key in CLUSTER_KEYS:
            assert mine[key] == theirs[key], (other, key)
        assert mine["source"] != theirs["source"]
    assert "2.4.6" in mine["source"] and "2.4.6.3" in mine["source"]
    assert queries["q06"]["reads"] == {"lineitem": [
        "l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]}
    assert set(queries["q06"]["reads"]["lineitem"]) <= set(
        mine["tables"]["lineitem"]["columns"])
    assert datacheck.faults(small, mine, 0.01) == []


def test_a_discount_off_the_hundredths_breaks_a_rule(small):
    """The reference decides the band in hundredths because the source
    says the column holds hundredths: the rule that says so is held."""
    from test_data import Broken
    mine = qgen.load_json("configs", "tpch_sf1_http_q06.json")
    broken = Broken(small, column={("lineitem", "l_discount"):
                                   lambda v: np.r_[v[:-1], v[-1] + 0.004]})
    found = datacheck.faults(broken, mine, 0.01)
    assert found and all("lineitem.l_discount" in f for f in found)


def test_q06_draws_all_eighty_triples(q06):
    query, _ref = q06
    seen = {json.dumps(qgen.statement(query, random.Random(k))[0],
                       sort_keys=True) for k in range(4000)}
    assert len(seen) == 5 * 8 * 2
    params, sql = qgen.statement(query, random.Random(7))
    assert params["DATE"] in sql and f"{params['DISCOUNT']} - 0.01" in sql
    assert f"l_quantity < {params['QUANTITY']}" in sql


# ---- the reference -------------------------------------------------------

SIX = [("1993-01-01", "0.02", 24), ("1994-01-01", "0.06", 24),
       ("1995-01-01", "0.09", 25), ("1996-01-01", "0.05", 25),
       ("1997-01-01", "0.04", 24), ("1994-01-01", "0.08", 25)]


@pytest.mark.parametrize("date, discount, quantity", SIX)
def test_the_reference_agrees_with_a_pandas_recomputation(
        small, q06, date, discount, quantity):
    """Another spelling of the same clause: dates as pandas timestamps,
    the band as the set of its three discounts written out, each
    compared as the text of two decimals."""
    _query, reference = q06
    li = pd.DataFrame({c: small.column("lineitem", c) for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_shipdate")})
    ship = pd.Timestamp("1970-01-01") + pd.to_timedelta(li.l_shipdate,
                                                        unit="D")
    first = pd.Timestamp(date)
    mid = int(discount[2:])
    band = {f"{(mid + d) / 100:.2f}" for d in (-1, 0, 1)}
    keep = ((ship >= first) & (ship < first + pd.DateOffset(years=1))
            & li.l_discount.map(lambda v: f"{v:.2f}").isin(band)
            & (li.l_quantity < quantity))
    want = float((li.l_extendedprice[keep] * li.l_discount[keep]).sum())
    (got,), = reference(small, {"DATE": date, "DISCOUNT": discount,
                                "QUANTITY": quantity})
    assert keep.sum() > 100
    assert got == pytest.approx(want, rel=1e-12)
    year = datetime.date.fromisoformat(date).year
    assert keep.sum() == ((ship.dt.year == year)
                          & li.l_discount.round(2).between(
                              (mid - 1) / 100 - 1e-9, (mid + 1) / 100 + 1e-9)
                          & (li.l_quantity < quantity)).sum()


@pytest.mark.parametrize("seed", [3, 2**31 + 17, 123456789])
def test_float32_control_of_q06_is_not_correct(small, seed):
    _b, _c, _cfg, traffic, queries = bench_run.load_cell("q06_serial")
    verdict = control.control_run(small, traffic, queries, seed, 6)
    assert not verdict["correct"]
    failed = [k for k, s in verdict["compared"].items()
              if s["value"] > s["limit"]]
    assert failed == ["q06.max_rel_err"]      # 1e-8 and more at SF0.01
    assert verdict["compared"]["q06.wrong_cells"]["value"] == 0
    sound = control.control_run(small, traffic, queries, seed, 6,
                                dtype=np.float64)
    assert sound["correct"]
    assert all(s["value"] == 0 for s in sound["compared"].values())


# ---- faults under the timed path -----------------------------------------

@pytest.fixture
def caps_cache_apart(tmp_path, monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_CAPS_CACHE", str(tmp_path / "caps.json"))


def drive(capsys) -> dict:
    rc = bench_run.main(["--workload", "q06_serial", "--seed", "2147483699",
                         "--seconds", "3", "--trace", "0", "--allow-cpu",
                         "--sf", "0.01"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def drop_the_upper_edge(monkeypatch):
    """`between` keeps its lower comparison only."""
    from presto_tpu.expr import compile as expr_compile
    real = expr_compile._compare

    def no_upper(op, x, y):
        out = real(op, x, y)
        if op == "le" and x.type.is_floating:
            return expr_compile._bool(out.values | True, out.nulls)
        return out
    monkeypatch.setattr(expr_compile, "_compare", no_upper)


def bake_the_first_discount(monkeypatch):
    """Every call of a program gets the float64 inputs its first call
    got: the band of the worker's first statement, as a program that
    holds its decimal literals would keep it."""
    from presto_tpu.exec.program_cache import Program
    real = Program.__call__
    first = {}

    def baked(self, pages, params=()):
        kept = first.setdefault(id(self), params)
        params = tuple(k if p.dtype == np.float64 else p
                       for p, k in zip(params, kept))
        return real(self, pages, params)
    monkeypatch.setattr(Program, "__call__", baked)


def test_sound_run_of_q06_is_correct(capsys, caps_cache_apart):
    line = drive(capsys)
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] >= 3
    discounts = {w["params"]["DISCOUNT"] for w in line["run"]["wrong"]}
    assert discounts == set()
    assert [w["compiled"] > 0 for w in line["run"]["warm_up"]] == [
        True, False, False]
    assert line["run"]["compiled_in_window"] == 0
    assert line["compared"]["q06.max_rel_err"]["value"] < 1e-12


@pytest.mark.parametrize("fault", [drop_the_upper_edge,
                                   bake_the_first_discount])
def test_broken_timed_path_of_q06_is_not_correct(capsys, monkeypatch,
                                                 caps_cache_apart, fault):
    fault(monkeypatch)
    line = drive(capsys)
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert not line["correct"]
    assert line["compared"]["q06.max_rel_err"]["value"] > 1e-3
    assert line["run"]["wrong"]


# ---- the two readers -----------------------------------------------------

def _dispatch(start, first_call, params=None, program="jit_presto_A_1"):
    stats = {"program": program, "first_call": int(first_call)}
    if params is not None:
        stats["params"] = params
    return span_reduce.Span("dispatch", "T2", start, start + 0.01, stats)


@pytest.fixture
def ctx(monkeypatch):
    """A window of 10 s, four statements: a worker pair's scan programs
    with five inputs each and a final aggregation with none a statement;
    one dispatch before the window (warm-up's, a miss) and one after."""
    spans = [_dispatch(99.0, True, 5)]
    for k in range(4):
        t = 100.5 + 2 * k
        spans += [_dispatch(t, False, 5), _dispatch(t + 0.1, False, 5),
                  _dispatch(t + 0.5, k == 2, 0, "jit_presto_Output_2")]
    spans += [_dispatch(111.0, True, 5),
              span_reduce.Span("upload", "T2", 100.4, 100.5, {"bytes": 1})]
    monkeypatch.setattr(span_reduce, "load", lambda path: {
        "spans": spans, "host": {}, "modules": {"/device:TPU:0": []}})
    return {"records": [{}] * 4, "trace": {
        "path": "fixture", "lo_s": 100.0, "hi_s": 110.0,
        "busiest": "/device:TPU:0", "devices": {"/device:TPU:0": []},
        "compiling": [], "in_statement": []}}


def _read(name, ctx):
    return qgen.load_py("layer_metrics", name + ".py").read(ctx)


def test_the_two_readers_count_the_windows_dispatches(ctx):
    # one miss (statement 2's final aggregation) over four statements
    assert _read("program_misses_per_stmt", ctx) == pytest.approx(0.25)
    # (5 + 5 + 0) a statement
    assert _read("literal_inputs_per_stmt", ctx) == pytest.approx(10.0)
    for s in span_reduce.load("fixture")["spans"]:
        s.stats["first_call"] = 0
    assert _read("program_misses_per_stmt", ctx) == 0.0   # said, not silent


@pytest.mark.parametrize("name", READERS)
def test_a_reader_is_silent_where_the_spans_do_not_say(name, ctx):
    assert _read(name, {"records": [{}], "trace": None}) is None
    assert _read(name, dict(ctx, records=[])) is None
    # the parent commit's spans: `first_call`, and no `params`
    for s in span_reduce.load("fixture")["spans"]:
        s.stats.pop("params", None)
    if name == "literal_inputs_per_stmt":
        assert _read(name, ctx) is None
    else:
        assert _read(name, ctx) == pytest.approx(0.25)
    span_reduce.load("fixture")["spans"].clear()
    assert _read(name, ctx) is None


def test_the_span_readers_are_the_benchmarks_entries():
    """Every assertion of `test_spans.py::
    test_the_readers_are_the_benchmarks_entries` and of `test_q18.py::
    test_the_span_readers_are_the_benchmarks_entries`, each of which pins
    its readers as the *last* entries of `per_layer` and so fails on that
    line once entries follow (new entries go to the end; neither file is
    a cell-adding PR's to edit): PR 26's thirteen and PR 29's two are
    there, together and in order, with the sources and `workloads` lists
    they had, and after them come only this PR's two."""
    import test_q18
    import test_spans
    pinned = list(test_spans.NEW_READERS) + list(test_q18.READERS)
    assert len(pinned) == 15
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert len(entries) == len(names) and set(pinned) <= set(entries)
    since = len(READERS)
    assert names[-15 - since:-since] == pinned
    assert names[-since:] == list(READERS)
    assert {entries[n]["source"] for n in test_spans.NEW_READERS} == {
        "program_span", "program_counter", "device_trace"}
    assert {n for n in test_spans.NEW_READERS
            if "workloads" in entries[n]} == {
        "exchange_pull_ms_per_stmt", "join_device_ms_per_stmt",
        "aggregate_device_ms_per_stmt"}
    for name in test_q18.READERS:
        assert entries[name]["workloads"] == ["q18_serial"]
        assert entries[name]["source"] == "device_trace"
    for name in READERS:
        assert entries[name]["workloads"] == ["q06_serial"]
        assert entries[name]["source"] == "program_span"
        assert entries[name]["moves"] == "wall_p50_s"
        assert entries[name]["layer"] == "worker task: lower + compile"
    # the one entry this PR was allowed to touch: a list of the cells in
    # which its reader finds something to read
    assert entries["compile_s_per_stmt"]["workloads"] == [
        "q03_serial", "q18_serial"]
