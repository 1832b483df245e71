"""What `q13_serial` adds to the benchmark, held where a CPU can hold it:
the template is fixed at the validation words; the plain reference on
hand-built tables (a customer with no order, one whose orders the NOT
LIKE all drops, one with both, a comment with the words in the other
order); on the connector's SF0.01 data the comparison catches an altered
count and half the orders left out (every cell is a BIGINT, so there is
no float32 control: a fault has to show as a wrong cell); the cell's
configuration lays out `tpch_sf1_http`'s cluster and keeps its rules,
and a planted fault in a key Q13 reads breaks one; the two readers on a
hand-made traced window, and where they find nothing; the order pin of
`test_served_span_readers.py`, which the two readers outgrow, on the
entries before them. Seconds, no cluster, no jit.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_q13.py -q
"""

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

import compare  # noqa: E402
import datacheck  # noqa: E402
import qgen  # noqa: E402
import run as bench_run  # noqa: E402
import span_reduce  # noqa: E402

VALIDATION = {"WORD1": "special", "WORD2": "requests"}
READERS = ("left_join_device_ms_per_stmt", "exchange_dict_words_per_stmt")


def test_q13_is_fixed_at_the_validation_words():
    q13 = qgen.load_query("q13")
    drawn = {json.dumps(qgen.statement(q13, random.Random(s))[0],
                        sort_keys=True) for s in (0, 7, 2**31 + 17)}
    assert drawn == {json.dumps(VALIDATION, sort_keys=True)}
    _p, sql = qgen.statement(q13, random.Random(0))
    assert "o_comment not like '%special%requests%'" in sql
    assert "left outer join" in sql and "{" not in sql
    assert q13["reads"] == {"customer": ["c_custkey"],
                            "orders": ["o_orderkey", "o_custkey",
                                       "o_comment"]}
    assert q13["limits"]["wrong_cells"] == 0


def test_the_configuration_is_the_other_cells_cluster():
    """`q13_serial` runs on `q03_serial`'s cluster, data and session
    (every key run.py reads is the same); its own are the source's query,
    the guarantee stated for counts, and the comment on o_comment."""
    _b, cell, mine, traffic, queries = bench_run.load_cell("q13_serial")
    theirs = bench_run.load_cell("q03_serial")[2]
    assert cell["config"] == mine["name"] == "tpch_sf1_http_q13"
    assert (cell["chips"], traffic["clients"], traffic["cycle"]) == (
        1, 1, ["q13"])
    for key in ("connector", "scale_factor", "rows", "workers", "chips",
                "session_properties", "reduced", "assumed"):
        assert mine[key] == theirs[key], key
    # the guarantee is the siblings' less its doubles: every cell a count
    assert mine["guarantees"]["results"].startswith(
        "every row of every statement exact")
    assert "2.4.13" in mine["source"] and mine["source"] != theirs["source"]
    for table, columns in queries["q13"]["reads"].items():
        ruled = set(mine["tables"][table]["columns"]) | {"o_comment"}
        assert set(columns) <= ruled, table
    for table, rules in theirs["tables"].items():  # it drops no rule
        if table != "comment":
            assert rules == mine["tables"][table]
    assert "o_comment" in mine["tables"]["comment"]


class Hand:
    """Tables built by hand, as `run.Tables` serves them."""

    def __init__(self, custkeys, orders):
        comments = sorted({c for _k, _c, c in orders})
        self._cols = {
            ("customer", "c_custkey"): np.asarray(custkeys, np.int64),
            ("orders", "o_orderkey"): np.asarray(
                [k for k, _c, _t in orders], np.int64),
            ("orders", "o_custkey"): np.asarray(
                [c for _k, c, _t in orders], np.int64),
            ("orders", "o_comment"): np.asarray(
                [comments.index(t) for _k, _c, t in orders], np.int32)}
        self._words = {("orders", "o_comment"): comments}

    def column(self, table, col):
        return self._cols[(table, col)]

    def words(self, table, col):
        return self._words[(table, col)]


def test_the_reference_counts_what_the_left_join_keeps():
    """Customer 1 places no order; customer 2's two orders are both
    flagged; customer 4 has one flagged order of three, and one whose
    comment has `requests` before `special`, which the pattern keeps;
    customer 5 has one order. c_count: 1 -> 0, 2 -> 0, 4 -> 2, 5 -> 1,
    7 -> 0: three customers at 0, one at 2, one at 1."""
    tables = Hand([1, 2, 4, 5, 7], [
        (10, 2, "slyly special ironic requests wake"),
        (11, 2, "special requests"),
        (12, 4, "special foxes sleep among requests"),
        (13, 4, "requests haggle special accounts"),
        (14, 4, "quickly special"),
        (15, 5, "final requests"),
    ])
    ref = compare.load_reference(qgen.load_query("q13"))
    assert ref(tables, VALIDATION) == [[0, 3], [2, 1], [1, 1]]
    # without the filter every order counts: 2 -> 2, 4 -> 3, 5 -> 1
    assert ref(tables, {"WORD1": "nothing", "WORD2": "matches"}) == [
        [0, 2], [3, 1], [2, 1], [1, 1]]
    # ties on custdist order by c_count, descending
    assert ref(Hand([1, 2], [(1, 1, "a"), (2, 1, "b"), (3, 2, "c")]),
               VALIDATION) == [[2, 1], [1, 1]]


@pytest.fixture(scope="module")
def small():
    return bench_run.Tables(bench_run.make_connector("tpch", 0.01))


def test_at_sf001_the_filter_flags_under_one_percent_and_a_third_count_0(
        small):
    ref = compare.load_reference(qgen.load_query("q13"))
    rows = ref(small, VALIDATION)
    customers = len(small.column("customer", "c_custkey"))
    assert sum(r[1] for r in rows) == customers
    assert rows[0] == [0, customers // 3]  # every third key places none
    words = small.words("orders", "o_comment")
    codes = small.column("orders", "o_comment")
    flagged = np.array(["special" in w and "requests" in
                        w[w.index("special"):] for w in words])[codes]
    assert 0.004 < flagged.mean() < 0.010
    # the dictionary is nearly as large as the column it codes
    assert len(words) > 0.99 * len(codes)


def test_the_comparison_catches_an_altered_count_and_half_the_orders(small):
    """What `test_faults.py` does for a cell with floats, done on the
    answer itself: an altered count, and the answer over half the
    orders, each comes out not correct."""
    query = qgen.load_query("q13")
    ref = compare.load_reference(query)
    want = ref(small, VALIDATION)
    limits = {"q13": query["limits"]}
    rec = {"template": "q13", "params": VALIDATION}
    assert compare.judge([dict(rec, rows=want)], [want], limits)["correct"]
    altered = [list(r) for r in want]
    altered[0][1] += 1
    verdict = compare.judge([dict(rec, rows=altered)], [want], limits)
    assert not verdict["correct"]
    assert verdict["compared"]["q13.wrong_cells"]["value"] == 1

    class Half:
        def column(self, table, col):
            got = small.column(table, col)
            return got[:len(got) // 2] if table == "orders" else got

        def words(self, table, col):
            return small.words(table, col)

    half = ref(Half(), VALIDATION)
    assert not compare.judge([dict(rec, rows=half)], [want],
                             limits)["correct"]


@pytest.mark.parametrize("table, col, alter", [
    ("orders", "o_custkey", lambda v: np.r_[v[:-1], 3]),
    ("orders", "o_orderkey", lambda v: np.r_[v[:-1], v[0]]),
    ("customer", "c_custkey", lambda v: np.r_[v[:-1], v[0]]),
], ids=["a-custkey-multiple-of-3", "an-orderkey-twice",
        "a-custkey-twice"])
def test_a_fault_in_a_key_q13_reads_breaks_a_rule(small, table, col, alter):
    mine = qgen.load_json("configs", "tpch_sf1_http_q13.json")
    from test_data import Broken
    assert datacheck.faults(small, mine, 0.01) == []
    found = datacheck.faults(Broken(small, column={(table, col): alter}),
                             mine, 0.01)
    assert found and all(f"{table}.{col}" in f for f in found), found


# ---- the two readers, on a hand-made traced window -----------------------

WINDOW = (100.0, 110.0)
MODULES = [
    ("jit_presto_Join_aaaa1111(1)", 101.0, 1.0),      # LEFT
    ("jit_presto_Aggregation_cccc3333(3)", 102.5, 2.0),
    ("jit_presto_Join_bbbb2222(2)", 105.0, 0.25),     # INNER+LEFT
    ("jit_presto_Join_aaaa1111(1)", 106.0, 0.5),
    ("jit_presto_Join_dddd4444(4)", 107.0, 0.75),     # INNER
    ("jit_presto_Join_aaaa1111(1)", 109.5, 1.0),      # half inside
]


def _dispatch(program, joins):
    stats = {"program": program, "operators": "Join+RemoteSource"}
    if joins:
        stats["join_types"] = joins
    return ("dispatch", "T1", 100.5, 0.25, stats)


def _serialize(start, words):
    stats = {"bytes": 1000, "device_puts": 0, "buffers": 2}
    if words is not None:
        stats["dict_words"] = words
    return ("serialize", "T2", start, 0.5, stats)


def _events(words):
    return [_dispatch("jit_presto_Join_aaaa1111", "LEFT"),
            _dispatch("jit_presto_Join_bbbb2222", "INNER+LEFT"),
            _dispatch("jit_presto_Join_dddd4444", "INNER"),
            _dispatch("jit_presto_Aggregation_cccc3333", None),
            _serialize(99.0, None if words[0] is None else 10**6),
            _serialize(101.0, words[0]), _serialize(103.0, words[1]),
            _serialize(109.75, words[2])]


def _ctx(monkeypatch, events, statements=2):
    spans = [span_reduce.backdated(*e) for e in events]
    monkeypatch.setattr(span_reduce, "load", lambda path: {
        "spans": spans, "host": {},
        "modules": {"/device:TPU:0": list(MODULES)}})
    lo, hi = WINDOW
    return {"records": [{}] * statements, "trace": {
        "path": "fixture", "lo_s": lo, "hi_s": hi,
        "busiest": "/device:TPU:0", "devices": {"/device:TPU:0": []},
        "compiling": [], "in_statement": []}}


def _read(name, ctx):
    return qgen.load_py("layer_metrics", name + ".py").read(ctx)


def test_the_readers_read_the_window(monkeypatch):
    ctx = _ctx(monkeypatch, _events([1000, 0, 500]))
    # LEFT: (1.0 + 0.25 + 0.5 + the 0.5 s the window keeps) s / 2
    assert _read("left_join_device_ms_per_stmt", ctx) == \
        pytest.approx(1125.0)
    # 1000 + 0 + half of 500 (the span the window's end cuts) over 2; the
    # span before the window is not counted
    assert _read("exchange_dict_words_per_stmt", ctx) == \
        pytest.approx(625.0)


def test_serialize_spans_that_wrote_no_dictionary_read_0(monkeypatch):
    ctx = _ctx(monkeypatch, _events([0, 0, 0]))
    got = _read("exchange_dict_words_per_stmt", ctx)
    assert got == 0.0 and got is not None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_where_the_program_does_not_say(
        monkeypatch, name):
    assert _read(name, {"records": [{}], "trace": None}) is None
    ctx = _ctx(monkeypatch, _events([1000, 0, 500]))
    assert _read(name, dict(ctx, records=[])) is None
    # a program whose spans lack the attributes (no `join_types`, no
    # `dict_words`): nothing to read, and no error
    bare = [(n, t, s, d, {k: v for k, v in st.items()
                          if k not in ("join_types", "dict_words")})
            for n, t, s, d, st in _events([1000, 0, 500])]
    assert _read(name, _ctx(monkeypatch, bare)) is None


def test_the_readers_are_entries_of_the_benchmark():
    """Both readers are in `per_layer`, listing the one cell they read,
    in the layers BENCHMARK.json already names. Where they stand in the
    list is left free: a later entry goes after them."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in READERS}
    for name in READERS:
        m = entries[name]
        assert m["workloads"] == ["q13_serial"]
        assert m["moves"] == "wall_p50_s" and m["layer"] in layers
    assert entries["left_join_device_ms_per_stmt"]["source"] == \
        "device_trace"
    assert entries["exchange_dict_words_per_stmt"]["source"] == \
        "program_span"


def test_the_served_span_readers_order_pin_holds_with_entries_after_it(
        monkeypatch):
    """`test_served_span_readers.py::
    test_the_readers_are_the_benchmarks_last_entries` pins the four
    readers as the *last* entries of `per_layer`, so it fails on that
    line once a cell's readers follow them. Here it runs whole on the
    benchmark with the entries since taken off the end; each of those
    lists its cells."""
    import test_served_span_readers as served
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    end = names.index(served.READERS[-1]) + 1
    since = bench["per_layer"][end:]
    assert {m["name"] for m in since} >= set(READERS)
    assert all(m.get("workloads") for m in since)
    trimmed = dict(bench, per_layer=bench["per_layer"][:end])
    monkeypatch.setattr(json, "load", lambda _f: trimmed)
    served.test_the_readers_are_the_benchmarks_last_entries()
