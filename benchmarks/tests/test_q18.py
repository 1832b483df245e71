"""What `q18_serial` adds to the benchmark, held where a CPU can hold it:
the template is fixed at the validation parameter; the float32 control
comes out not correct and the float64 reference passes itself, at SF0.01
with a threshold that keeps rows there (250: the template's own 300
keeps none under SF0.05) and at SF0.1 with the template's own; the two
readers of the `dispatch` attributes on a hand-made window
(`fixtures/dispatch_attr_events.json`); the cell's configuration,
`tpch_sf1_http_q18`, lays out `tpch_sf1_http`'s cluster and adds the
source's rules for `o_totalprice` and `c_name`, each of which a planted
fault breaks. Seconds, no cluster, no jit.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_q18.py -q
"""

import copy
import json
import os
import random
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import datacheck  # noqa: E402
import dispatch_attrs  # noqa: E402
import qgen  # noqa: E402
import run as bench_run  # noqa: E402
import span_reduce  # noqa: E402

READERS = ("semi_join_device_ms_per_stmt",
           "partial_aggregate_device_ms_per_stmt")


def with_quantity(queries: dict, quantity: int) -> dict:
    out = copy.deepcopy(queries)
    out["q18"]["params"]["QUANTITY"]["value"] = quantity
    return out


def test_q18_is_fixed_at_the_validation_parameter():
    q18 = qgen.load_query("q18")
    drawn = {json.dumps(qgen.statement(q18, random.Random(s))[0])
             for s in range(20)}
    assert drawn == {json.dumps({"QUANTITY": 300})}
    _p, sql = qgen.statement(q18, random.Random(0))
    assert "sum(l_quantity) > 300" in sql and "{" not in sql


@pytest.mark.parametrize("sf, quantity, n_rows", [
    (0.01, 250, 79), (0.1, 300, 9)])
@pytest.mark.parametrize("seed", [3, 2**31 + 17, 123456789])
def test_float32_control_of_q18_is_not_correct(sf, quantity, n_rows, seed):
    _b, _c, _cfg, traffic, queries = bench_run.load_cell("q18_serial")
    queries = with_quantity(queries, quantity)
    tables = bench_run.Tables(bench_run.make_connector("tpch", sf))
    verdict = control.control_run(tables, traffic, queries, seed, 2)
    assert not verdict["correct"]
    failed = [k for k, s in verdict["compared"].items()
              if s["value"] > s["limit"]]
    assert failed == ["q18.max_rel_err"]  # o_totalprice, 1.4e-8 to 5e-8
    sound = control.control_run(tables, traffic, queries, seed, 2,
                                dtype=np.float64)
    assert sound["correct"]
    assert all(s["value"] == 0 for s in sound["compared"].values())
    ref = bench_run.compare.load_reference(queries["q18"])
    rows = ref(tables, {"QUANTITY": quantity})
    assert len(rows) == n_rows
    assert [r[4] for r in rows] == sorted((r[4] for r in rows),
                                          reverse=True)
    assert all(r[5] > quantity and r[0] == f"Customer#{r[1]:09d}"
               for r in rows)


def test_the_configuration_is_the_other_cells_cluster():
    """`q18_serial` is there to be the other side of `q03_serial`: same
    data, scale, cluster and session; its own are the source's query and
    the rules for the columns that query reads."""
    _b, cell, mine, _t, queries = bench_run.load_cell("q18_serial")
    theirs = bench_run.load_cell("q03_serial")[2]
    assert cell["config"] == mine["name"] == "tpch_sf1_http_q18"
    for key in ("connector", "scale_factor", "rows", "workers", "chips",
                "session_properties", "guarantees", "reduced", "assumed"):
        assert mine[key] == theirs[key], key
    assert "2.4.18" in mine["source"] and mine["source"] != theirs["source"]
    for table, columns in queries["q18"]["reads"].items():
        ruled = set(mine["tables"][table]["columns"]) | {
            mine["tables"][table].get("per_parent", {}).get("column")}
        assert set(columns) <= ruled, table
    for table, rules in theirs["tables"].items():  # it drops no rule
        if table != "comment":
            assert rules["columns"].items() <= \
                mine["tables"][table]["columns"].items()


Q18_FAULTS = {
    "a price in thousandths": ("orders", "o_totalprice",
                               lambda v: np.r_[v[:-1], v[-1] + 0.004]),
    "a price under one line's least": ("orders", "o_totalprice",
                                       lambda v: np.r_[v[:-1], 809.99]),
    "a price over seven lines' most": ("orders", "o_totalprice",
                                       lambda v: np.r_[793422.01, v[1:]]),
    "two customers of one name": ("customer", "c_name",
                                  lambda v: np.r_[v[:-1], v[0]]),
    "a name from outside the dictionary": (
        "customer", "c_name", lambda v: np.r_[v[:-1], len(v) + 1]),
}


@pytest.fixture(scope="module")
def small():
    return bench_run.Tables(bench_run.make_connector("tpch", 0.01))


@pytest.mark.parametrize("fault", sorted(Q18_FAULTS))
def test_a_fault_in_a_column_q18_reads_breaks_a_rule(small, fault):
    mine = qgen.load_json("configs", "tpch_sf1_http_q18.json")
    theirs = qgen.load_json("configs", "tpch_sf1_http.json")
    from test_data import Broken
    table, col, alter = Q18_FAULTS[fault]
    broken = Broken(small, column={(table, col): alter})
    found = datacheck.faults(broken, mine, 0.01)
    assert found and all(f"{table}.{col}" in f or f.startswith(table + ":")
                         for f in found), found
    assert datacheck.faults(small, mine, 0.01) == []
    if col == "o_totalprice":  # the configuration that was there is blind
        assert datacheck.faults(broken, theirs, 0.01) == []


def test_a_customers_name_is_its_key_in_nine_digits(small):
    """What datacheck.py's rules cannot say of `c_name` (a string's
    form), and so the configuration's `unique` rule stands on."""
    words = small.words("customer", "c_name")
    codes = small.column("customer", "c_name")
    keys = small.column("customer", "c_custkey")
    assert [words[int(c)] for c in codes] == [
        f"Customer#{k:09d}" for k in keys]


@pytest.fixture(scope="module")
def fx():
    return qgen.load_json("fixtures", "dispatch_attr_events.json")


@pytest.fixture
def ctx(fx, monkeypatch):
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


def _read(name, ctx):
    return qgen.load_py("layer_metrics", name + ".py").read(ctx)


def test_the_two_readers_pick_their_programs_by_the_attributes(fx, ctx):
    want = fx["expected"]
    spans = span_reduce.load("fixture")["spans"]
    assert dispatch_attrs.programs_with(spans, "join_types", "SEMI") == \
        set(want["programs_with_semi"])
    assert dispatch_attrs.programs_with(spans, "agg_steps", "PARTIAL") == \
        set(want["programs_with_partial"])
    assert dispatch_attrs.programs_with(spans, "join_types", "LEFT") == set()
    # SEMI: (0.125 + 0.25 + 0.75) s over 3 statements; PARTIAL: (1.5 + 1.5
    # + the 0.5 s the window keeps of the last execution + 0.25) s over 3
    for name in READERS:
        assert _read(name, ctx) == pytest.approx(want[name])


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_the_spans_lack_the_attribute(
        name, ctx):
    assert _read(name, {"records": [{}], "trace": None}) is None
    assert _read(name, dict(ctx, records=[])) is None
    # the parent commit's spans: `operators` and `join_paths`, no more
    for s in span_reduce.load("fixture")["spans"]:
        s.stats.pop("join_types", None)
        s.stats.pop("agg_steps", None)
    assert _read(name, ctx) is None
    # and a program without spans at all
    span_reduce.load("fixture")["spans"].clear()
    assert _read(name, ctx) is None


def test_the_span_readers_are_the_benchmarks_entries():
    """Every assertion of `test_spans.py::
    test_the_readers_are_the_benchmarks_entries`, which pinned PR 26's
    thirteen readers as the *last* thirteen entries of `per_layer` and is
    marked in `conftest.py` for that one line: they are there, together
    and in order, with the sources and the `workloads` lists they had;
    after them come only the entries added since, each with its cells
    listed."""
    import test_spans
    pinned = list(test_spans.NEW_READERS)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert len(entries) == len(names) and set(pinned) <= set(entries)
    since = len(READERS)
    assert names[-13 - since:-since] == pinned
    assert names[-since:] == list(READERS)
    assert {entries[n]["source"] for n in pinned} == {
        "program_span", "program_counter", "device_trace"}
    assert {n for n in pinned if "workloads" in entries[n]} == {
        "exchange_pull_ms_per_stmt", "join_device_ms_per_stmt",
        "aggregate_device_ms_per_stmt"}
    for name in READERS:
        assert entries[name]["workloads"] == ["q18_serial"]
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["moves"] == "wall_p50_s"
