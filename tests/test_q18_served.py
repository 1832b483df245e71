"""TPC-H Q18 on the served path's cluster (two workers, HTTP pages, the
defaults) against the benchmark's plain reference
(`benchmarks/queries/q18.py`: numpy and pandas over the connector's
arrays, nothing of the engine), at SF0.01. The validation parameter 300
keeps no order at this scale, and that has to agree too; 250 keeps 79
and 270 keeps 15. Each literal is a chain of programs of its own.

At SF0.01 orders and customer fall under `broadcast_join_threshold_rows`
(50,000) and are replicated: seven fragments, the three joins in one. At
SF1, where the benchmark's cell `q18_serial` runs, both lie above it and
every join is partitioned: eight fragments, the 6M-row probe exchanged
three times, the SEMI join beside a SINGLE aggregation. The last case
lowers the threshold to get that plan here. The 300 case also reads the
statement's `dispatch` spans for `join_types` and `agg_steps`.

Last, Q18 and Q3 (the benchmark's two templates) each three times through
`POST /v1/statement`: the workers keep their programs, so once the
capacities have settled a repeated statement compiles nothing."""

import os
import sys

import pytest

from presto_tpu.connectors import TpchConnector
from presto_tpu.protocol import serde
from presto_tpu.exec.program_cache import _PROGRAMS
from presto_tpu.server.cluster import TpuCluster
from presto_tpu.server.statement import StatementServer, run_statement
from presto_tpu.utils.tracing import TRACER

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare  # noqa: E402
from compile_counter import compile_counter  # noqa: E402
import qgen  # noqa: E402
import run as bench_run  # noqa: E402


@pytest.fixture(scope="module")
def q18():
    query = qgen.load_query("q18")
    return query, compare.load_reference(query)


@pytest.fixture(scope="module")
def connector():
    return TpchConnector(0.01)


def holds_what_each_program_joins_and_aggregates(dispatched):
    """`join_types` and `agg_steps` on the statement's `dispatch` spans:
    a JoinNode each beside `join_paths`, an AggregationNode each, and
    neither on a program that holds none. The benchmark's
    `semi_join_device_ms_per_stmt` and
    `partial_aggregate_device_ms_per_stmt` pick their programs by them."""
    joins = [a for a in dispatched if "join_types" in a]
    aggs = [a for a in dispatched if "agg_steps" in a]
    assert {t for a in joins for t in a["join_types"].split("+")} == {
        "INNER", "SEMI"}
    assert {t for a in aggs for t in a["agg_steps"].split("+")} == {
        "PARTIAL", "FINAL"}
    for a in dispatched:
        ops = a["operators"].split("+")
        assert ("join_types" in a) == ("Join" in ops) == ("join_paths" in a)
        assert len(a.get("join_types", "").split("+")) == \
            len(a.get("join_paths", "").split("+"))
        assert ("agg_steps" in a) == ("Aggregation" in ops)


@pytest.mark.parametrize("quantity, n_rows, session", [
    (250, 79, None), (270, 15, None), (300, 0, None),
    (250, 79, {"broadcast_join_threshold_rows": 100})],
    ids=["250", "270", "300", "250-partitioned-as-at-sf1"])
def test_q18_agrees_with_the_plain_reference(connector, q18, quantity,
                                             n_rows, session):
    query, reference = q18
    sql = query["sql"].format(QUANTITY=quantity)
    want = reference(bench_run.Tables(connector), {"QUANTITY": quantity})
    cluster = TpuCluster(connector, n_workers=2, session_properties=session)
    decode_hits = serde._DICTIONARY.value(side="decode", result="hit")
    try:
        got = cluster.execute_sql(sql)
        spans = TRACER.get(cluster.last_trace_id)
        dispatched = [s.attributes for s in spans if s.name == "dispatch"]
        fragments = len(cluster._fragment_plan(cluster.plan_sql(sql),
                                               None)[2])
    finally:
        cluster.stop()
    assert fragments == (8 if session else 7)
    assert len(want) == n_rows
    # DATE comes as days since 1970-01-01 on both sides; the doubles are
    # whole quantities and prices in cents, exact in float64
    assert [list(r) for r in got] == want
    # c_name's dictionary crosses once: the pages after its first find it
    # decoded (protocol/serde), and the spans around the work say so
    decode_hits = serde._DICTIONARY.value(side="decode",
                                          result="hit") - decode_hits
    assert decode_hits > 0
    assert sum(s.attributes.get("dict_hits", 0) for s in spans
               if s.name == "deserialize") == decode_hits
    assert sum(s.attributes.get("dict_hits", 0)
               + s.attributes.get("dict_misses", 0) for s in spans
               if s.name == "serialize") > 0
    if quantity == 300:
        holds_what_each_program_joins_and_aggregates(dispatched)


@pytest.mark.parametrize("name, params", [
    ("q18", {"QUANTITY": 250}),
    ("q03", {"SEGMENT": "BUILDING", "DATE": "1995-03-15"})],
    ids=["q18", "q03"])
def test_a_repeated_statement_compiles_nothing(connector, name, params):
    """The first statement learns its capacities and the second runs the
    annealed variants (a checkout whose caps file has converged skips
    both); from then on every island of every task is a program its
    worker has kept (`presto_tpu_program_cache_total`) under dictionaries
    `jax.jit` has seen: no trace, no lowering, no compile request."""
    query = qgen.load_query(name)
    sql = query["sql"].format(**params)
    want = compare.load_reference(query)(bench_run.Tables(connector), params)
    cluster = TpuCluster(connector, n_workers=2)
    server = StatementServer(cluster).start()
    counter = compile_counter()
    runs = []
    try:
        for _ in range(3):
            requests = counter.requests
            misses = _PROGRAMS.value(result="miss")
            hits = _PROGRAMS.value(result="hit")
            _columns, rows = run_statement(server.base, sql)
            runs.append((rows, counter.requests - requests,
                         _PROGRAMS.value(result="miss") - misses,
                         _PROGRAMS.value(result="hit") - hits))
    finally:
        server.stop()
        cluster.stop()
    (first, compiled, missed, _hit), _second, (third, *steady) = runs
    assert compiled > 0 and missed > 0
    assert steady[:2] == [0, 0] and steady[2] > 0
    assert third == first and len(third) == len(want) > 0
    gaps = compare.row_gaps(third, want)
    assert gaps["wrong_cells"] == 0
    assert gaps["max_rel_err"] <= query["limits"]["max_rel_err"]
