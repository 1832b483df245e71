"""The TPC-H generator: every array and every dictionary's words as they
were when every string column was built from Python strings at once,
fixed-vocabulary columns drawn as codes, and text columns made on first
read, once, under a `generate` span and the connector's counters."""

import hashlib
import json
import os
import sys
import threading

import numpy as np
import pytest

from presto_tpu.connectors import TpchConnector, tpch
from presto_tpu.data.column import StringDict
from presto_tpu.utils.tracing import TRACER, trace_scope

#: md5 over every column of a table (`_table_digest`), taken from the
#: generator that built every string column from its words
PINNED = {
    ("region", 0.001): "f345e22ced49e7f98d98ba84f42435f5",
    ("nation", 0.001): "75cd6b1a4af729f417b7c2acf8b331c4",
    ("supplier", 0.001): "a54c2f7811bdeac11c55d2c69c1b637b",
    ("customer", 0.001): "7141ce008140629c171354fb9894c1d0",
    ("part", 0.001): "0e4a12d4dc948f7ee34393bd99233f58",
    ("partsupp", 0.001): "30014ff3b87212b42af07877ec66cbb5",
    ("orders", 0.001): "6fc28617fea4334c67ab99e2624b4355",
    ("lineitem", 0.001): "982b9b7866ae9c733046fd3aed9348c7",
    ("region", 0.01): "a14cbbc969d411d4952e5ada4d9ed45c",
    ("nation", 0.01): "831b14a766f49c7c089ae7a11db2e032",
    ("supplier", 0.01): "31105da790cb54f3d4b50cb0b2ca2244",
    ("customer", 0.01): "1981b9c716c972e4c36a7a37cbe4e1d3",
    ("part", 0.01): "783f71411885434136cb7a81ab1fbcfe",
    ("partsupp", 0.01): "63e226d5958366b83063b55129275769",
    ("orders", 0.01): "31b70e3b1bd53653739428f8a9770581",
    ("lineitem", 0.01): "dae716c5b4c43e26337e202e242b6267",
}
#: `_column_digest` of lineitem.l_comment at SF0.01, likewise
L_COMMENT_SF001 = "83816020bef86cf06da9bf830701650c"

CODED = [("region", "r_name"), ("nation", "n_name"),
         ("customer", "c_mktsegment"), ("part", "p_mfgr"),
         ("part", "p_brand"), ("part", "p_type"), ("part", "p_container"),
         ("orders", "o_orderstatus"), ("orders", "o_orderpriority"),
         ("orders", "o_clerk"), ("lineitem", "l_returnflag"),
         ("lineitem", "l_linestatus"), ("lineitem", "l_shipinstruct"),
         ("lineitem", "l_shipmode")]
TEXT = {"region": ["r_comment"], "nation": ["n_comment"],
        "supplier": ["s_name", "s_address", "s_phone", "s_comment"],
        "customer": ["c_name", "c_address", "c_phone", "c_comment"],
        "part": ["p_name", "p_comment"], "partsupp": ["ps_comment"],
        "orders": ["o_comment"], "lineitem": ["l_comment"]}


def _column_digest(t, c: str) -> str:
    a = t.arrays[c][:t.num_rows]
    h = hashlib.md5(f"{a.dtype.str}|{a.shape}".encode())
    h.update(a.tobytes())
    d = t.dicts.get(c)
    if d is not None:
        h.update(json.dumps(list(d.words)).encode())
    return h.hexdigest()


def _table_digest(t) -> str:
    cols = {c: _column_digest(t, c) for c in t.column_names()}
    return hashlib.md5(json.dumps(cols, sort_keys=True).encode()).hexdigest()


def _fresh(name: str, sf: float = 0.01):
    """A table of its own, not the process's cached one."""
    if name in ("orders", "lineitem"):
        return tpch._gen_orders_lineitem.__wrapped__(name, sf)
    return tpch._gen_table.__wrapped__(name, sf)


@pytest.mark.parametrize("name, sf", sorted(PINNED),
                         ids=[f"{n}-sf{s}" for n, s in sorted(PINNED)])
def test_every_array_and_dictionary_is_as_pinned(name, sf):
    assert _table_digest(TpchConnector(sf).table(name)) == PINNED[name, sf]


@pytest.mark.parametrize("name, col", CODED, ids=[c for _t, c in CODED])
def test_a_coded_column_is_what_stringdict_build_gives(name, col):
    t = _fresh(name)
    assert col in t.arrays.made() and col in t.dicts.made()
    codes, d = t.arrays[col], t.dicts[col]
    want_dict, want_codes = StringDict.build(
        np.asarray(d.words, dtype=object)[codes])
    assert want_dict.words == d.words
    assert codes.dtype == want_codes.dtype == np.int32
    assert np.array_equal(codes, want_codes)


@pytest.mark.parametrize("name", sorted(TEXT))
def test_text_columns_wait_for_their_first_read(name):
    t = _fresh(name)
    others = [c for c in t.column_names() if c not in TEXT[name]]
    assert sorted(t.arrays.made()) == sorted(others)
    assert not set(TEXT[name]) & set(t.dicts.made())
    # every column is in the mappings, made or not
    assert list(t.arrays) == t.column_names()
    assert all(c in t.arrays and c in t.dicts for c in TEXT[name])


def test_a_split_and_a_page_of_other_columns_make_no_text():
    t = _fresh("lineitem")
    view = t.split(1, 2)
    page = view.page(columns=["l_quantity", "l_returnflag"])
    assert int(page.num_rows) == view.num_rows
    window = t.row_slice(10, 20)
    assert window.arrays["l_shipmode"].shape == (10,)
    for m in (t.arrays, view.arrays, window.arrays):
        assert "l_comment" not in m.made()
    assert sorted(window.arrays.made()) == ["l_shipmode"]
    assert sorted(view.arrays.made()) == ["l_quantity", "l_returnflag"]
    # a window's column is the parent's, made on its first read
    assert np.array_equal(window.arrays["l_comment"],
                          t.arrays["l_comment"][10:20])
    assert window.dicts["l_comment"] is t.dicts["l_comment"]


def test_threads_that_read_at_once_make_a_text_column_once():
    """More readers than cores, through both mappings and two split
    views, with the interpreter switching threads as often as it can."""
    t = _fresh("lineitem")
    views = [t.split(p, 2) for p in (0, 1)]
    before = tpch._GENERATED_BYTES.value(table="lineitem")
    readers = 4 * (os.cpu_count() or 4)
    start = threading.Barrier(readers)
    got = [None] * readers

    def read(i):
        start.wait()
        got[i] = (t.dicts["l_comment"] if i % 3 == 0 else
                  views[i % 2].arrays["l_comment"] if i % 3 == 1 else
                  t.arrays["l_comment"])
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=read, args=(i,))
                   for i in range(readers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(th.is_alive() for th in threads)
    codes, d = t.arrays["l_comment"], t.dicts["l_comment"]
    for i, g in enumerate(got):
        want = (d if i % 3 == 0 else
                views[i % 2].arrays["l_comment"] if i % 3 == 1 else codes)
        assert g is want
    assert np.array_equal(np.concatenate(
        [v.arrays["l_comment"] for v in views]), codes)
    assert tpch._GENERATED_BYTES.value(table="lineitem") - before == (
        codes.nbytes + sum(map(len, d.words)))
    assert _column_digest(t, "l_comment") == L_COMMENT_SF001


def test_each_text_column_made_has_one_span_and_counts():
    t = _fresh("customer")
    bytes0 = tpch._GENERATED_BYTES.value(table="customer")
    secs0 = tpch._GENERATE_SECONDS.value(table="customer")
    with trace_scope("tpch-generate-test"):
        phone = t.arrays["c_phone"]
        address = t.dicts["c_address"]     # the dictionary first
        t.arrays["c_phone"], t.dicts["c_phone"], t.arrays["c_address"]
    spans = [s for s in TRACER.get("tpch-generate-test")
             if s.name == "generate"]
    assert [s.attributes["column"] for s in spans] == ["c_phone",
                                                       "c_address"]
    made = {"c_phone": (phone, t.dicts["c_phone"]),
            "c_address": (t.arrays["c_address"], address)}
    for s in spans:
        codes, d = made[s.attributes["column"]]
        assert s.attributes["table"] == "customer"
        assert s.attributes["rows"] == t.num_rows == 1500
        assert s.attributes["bytes"] == codes.nbytes + sum(map(len, d.words))
        assert s.end >= s.start
    assert tpch._GENERATED_BYTES.value(table="customer") - bytes0 == sum(
        s.attributes["bytes"] for s in spans)
    assert tpch._GENERATE_SECONDS.value(table="customer") > secs0


def test_the_counters_are_on_the_metrics_page():
    from presto_tpu.obs.metrics import render_prometheus
    _fresh("region").arrays["r_comment"]
    text = render_prometheus()
    assert 'presto_tpu_connector_generated_bytes_total{table="region"}' \
        in text
    assert 'presto_tpu_connector_generate_seconds_total{table="region"}' \
        in text


def test_lazy_columns_with_and_without_names():
    made = []

    def make(c):
        if c == "missing":
            raise KeyError(c)
        made.append(c)
        return np.arange(3) + len(made)
    named = tpch.LazyColumns(make, ["a", "b"], {"a": np.zeros(3)})
    assert len(named) == 2 and list(named) == ["a", "b"]
    assert "b" in named and "c" not in named and named.made() == ["a"]
    assert named.get("c") is None and made == []
    assert named["b"] is named.get("b") and made == ["b"]
    open_ended = tpch.LazyColumns(make)
    assert len(open_ended) == 0 and "x" not in open_ended
    assert open_ended.get("missing") is None
    open_ended["x"]
    assert list(open_ended) == ["x"] and "x" in open_ended
