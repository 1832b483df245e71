"""TPC-H Q13 on the served path's cluster (two workers, HTTP pages,
`POST /v1/statement`) against the benchmark's plain reference
(`benchmarks/queries/q13.py`: a Python regular expression and a pandas
LEFT merge over the connector's arrays, nothing of the engine), at
SF0.01: the validation words the cell `q13_serial` runs and two more of
qgen's pairs, each a chain of programs of its own.

The planner pushes the NOT LIKE under the LEFT join, into the orders
scan's fragment. At SF0.01 the filtered orders fall under
`broadcast_join_threshold_rows` and are replicated; at SF1, where the
cell runs, both sides are hash-partitioned, and the last case lowers the
threshold to get that plan here. The LIKE is a host pass over the
comment dictionary at lowering (`dict_predicate` span,
`presto_tpu_dict_predicate_words_total`); `o_comment` crosses the
exchange with its dictionary whole in every frame (`serialize` span,
`dict_words`), decoded once a receiver (`deserialize`, `dict_words`).
CPU: counts and answers, never rates."""

import os
import re
import sys

import pytest

from presto_tpu.connectors import TpchConnector
from presto_tpu.expr import compile as expr_compile
from presto_tpu.plan.nodes import ExchangeNode, FilterNode, JoinNode, \
    TableScanNode
from presto_tpu.protocol import serde
from presto_tpu.server.cluster import TpuCluster
from presto_tpu.server.statement import StatementServer, run_statement
from presto_tpu.utils.tracing import TRACER

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare  # noqa: E402
import qgen  # noqa: E402
import run as bench_run  # noqa: E402

VALIDATION = {"WORD1": "special", "WORD2": "requests"}
PAIRS = [VALIDATION, {"WORD1": "pending", "WORD2": "deposits"},
         {"WORD1": "express", "WORD2": "packages"}]


@pytest.fixture(scope="module")
def connector():
    return TpchConnector(0.01)


@pytest.fixture(scope="module")
def q13():
    query = qgen.load_query("q13")
    return query, compare.load_reference(query)


@pytest.fixture(scope="module")
def served(connector):
    """(statement server's base, the cluster): one cluster for the
    module, so a pair asked again runs the programs it built."""
    cluster = TpuCluster(connector, n_workers=2)
    srv = StatementServer(cluster).start()
    try:
        yield srv.base, cluster
    finally:
        srv.stop()
        cluster.stop()


def _matching(connector, params) -> int:
    """Words of the comment dictionary that LIKE '%WORD1%WORD2%' keeps,
    counted apart from both the engine and the reference."""
    rx = re.compile(re.escape(params["WORD1"]) + ".*"
                    + re.escape(params["WORD2"]))
    return sum(1 for w in connector.table("orders").dicts["o_comment"].words
               if rx.search(w))


def _serialized_by_fragment(spans) -> dict:
    """fragment -> the `dict_words` of each of its `serialize` spans,
    through the span's `task_run` ancestor."""
    by_id = {s.span_id: s for s in spans}
    out = {}
    for s in spans:
        if s.name != "serialize":
            continue
        up = by_id.get(s.parent_id)
        while up is not None and up.name != "task_run":
            up = by_id.get(up.parent_id)
        out.setdefault(up.attributes["fragment"], []).append(
            s.attributes["dict_words"])
    return out


@pytest.mark.parametrize("params", PAIRS,
                         ids=["-".join(p.values()) for p in PAIRS])
def test_q13_agrees_with_the_plain_reference(served, connector, q13,
                                             params):
    """Every row exact, and the LIKE's host pass seen where it ran: once
    a worker, on lowering the orders fragment, over the whole comment
    dictionary, keeping the words that match."""
    base, cluster = served
    query, reference = q13
    before = expr_compile._DICT_PREDICATE_WORDS.value()
    _cols, rows = run_statement(base, query["sql"].format(**params))
    want = reference(bench_run.Tables(connector), params)
    # a third of the customers place no order: c_count 0 is the largest
    # group, and the count of every customer is there
    assert want[0][0] == 0 and sum(r[1] for r in want) == int(
        connector.table("customer").num_rows)
    assert [list(r) for r in rows] == want
    assert compare.row_gaps([list(r) for r in rows], want) == {
        "wrong_cells": 0, "max_rel_err": 0.0}
    fired = [s.attributes for s in TRACER.get(cluster.last_trace_id)
             if s.name == "dict_predicate"]
    words = len(connector.table("orders").dicts["o_comment"])
    assert len(fired) == len(cluster.workers)
    assert all(a == {"words": words, "kept": _matching(connector, params),
                     "fn": "like"} for a in fired)
    assert expr_compile._DICT_PREDICATE_WORDS.value() - before == \
        words * len(fired)


def test_a_repeated_statement_at_the_same_words_misses_no_program(
        served, connector, q13):
    """The validation words again, after the case above asked them once
    and learned the capacities: the next statement runs the annealed
    variants (two programs a worker at SF0.01; the cell's warm-up runs
    until a statement compiles what the one before did), and from then
    on each worker's program cache holds what the statement runs (no
    miss, so no trace, no lowering: no `dict_predicate`), with the same
    answer."""
    base, cluster = served
    query, reference = q13
    sql = query["sql"].format(**VALIDATION)

    def kept(worker):
        return set(worker.task_manager.programs.jitted.values())

    _cols, first = run_statement(base, sql)     # the annealed variants
    programs = [kept(w) for w in cluster.workers]
    _cols, rows = run_statement(base, sql)
    assert [kept(w) for w in cluster.workers] == programs
    spans = TRACER.get(cluster.last_trace_id)
    assert not any(s.attributes.get("first_call") for s in spans
                   if s.name == "dispatch")
    assert not any(s.name == "dict_predicate" for s in spans)
    assert rows == first
    assert [list(r) for r in rows] == reference(bench_run.Tables(connector),
                                                VALIDATION)


def _left_join_over_filtered_orders(fragments):
    """The LEFT JoinNode and the fragment its build side reads."""
    def walk(node):
        yield node
        for child in node.children():
            yield from walk(child)

    joins = [n for f in fragments for n in walk(f.root)
             if isinstance(n, JoinNode)]
    assert [j.join_type.name for j in joins] == ["LEFT"]
    join = joins[0]
    assert isinstance(join.build, ExchangeNode)
    built = {f.fragment_id: f for f in fragments}[join.build.remote_fragment]
    return join, built


@pytest.mark.parametrize("session, kind", [
    (None, "BROADCAST"), ({"broadcast_join_threshold_rows": 100}, "HASH")],
    ids=["broadcast-as-at-sf001", "partitioned-as-at-sf1"])
def test_the_not_like_lies_under_the_left_join_and_its_words_cross(
        connector, q13, session, kind):
    """The fragments: one LEFT join whose build side is the orders scan's
    fragment, a FilterNode holding NOT LIKE over the scan; replicated at
    this scale, hash-partitioned (both sides) as at SF1. The statement
    agrees with the reference. The orders fragment's `serialize` spans
    carry the comment dictionary's words once a frame (one frame for a
    broadcast, one a partition), every other fragment's carry 0: no
    string column crosses there. A receiver counts the dictionary's
    words where it decodes them (`deserialize`'s `dict_words`), and 0
    where the digest finds them kept."""
    query, reference = q13
    sql = query["sql"].format(**VALIDATION)
    cluster = TpuCluster(connector, n_workers=2, session_properties=session)
    try:
        fragments = cluster._fragment_plan(cluster.plan_sql(sql), None)[2]
        serde._DICT_CACHE.clear()
        rows = cluster.execute_sql(sql)
        spans = TRACER.get(cluster.last_trace_id)
    finally:
        cluster.stop()
    join, orders = _left_join_over_filtered_orders(fragments)
    assert join.build.partitioning.name == kind
    assert isinstance(join.probe, ExchangeNode if kind == "HASH"
                      else TableScanNode)
    assert isinstance(orders.root, FilterNode)
    assert isinstance(orders.root.source, TableScanNode)
    assert orders.root.source.table == "orders"
    pred = orders.root.predicate
    assert pred.name == "not" and pred.args[0].name == "like"
    assert pred.args[0].args[1].value == "%special%requests%"
    assert [list(r) for r in rows] == reference(bench_run.Tables(connector),
                                                VALIDATION)
    words = len(connector.table("orders").dicts["o_comment"])
    frames = 2 if kind == "HASH" else 1
    shipped = _serialized_by_fragment(spans)
    assert shipped.pop(str(orders.fragment_id)) == [words * frames] * 2
    assert shipped and all(n == 0 for counts in shipped.values()
                           for n in counts)
    # a frame decodes its dictionary only where the digest misses
    # `_DICT_CACHE` (one for both workers of this process; two fetcher
    # threads may both miss before either has kept it)
    decoded = [(s.attributes["dict_words"], s.attributes.get(
        "dict_misses", 0)) for s in spans if s.name == "deserialize"]
    assert all(n == words * misses for n, misses in decoded)
    assert any(misses for _n, misses in decoded)
