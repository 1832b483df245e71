"""TPC-H Q1 with the substitution parameter of clause 2.4.1.3 on the
served path (two workers, HTTP pages, `POST /v1/statement`) against the
benchmark's plain reference (`benchmarks/queries/q01.py`: numpy over the
connector's arrays and the two dictionaries' words, nothing of the
engine), at SF0.01.

Nine DELTAs across 60..120, both ends and the validation parameter among
them; after the first two statements of the shape (the first learns the
capacities, the second runs the annealed variants) no DELTA misses a
worker's program cache or asks the compiler for anything: `date
'1998-12-01' - interval 'DELTA' day` folds to one DATE literal, an input
of the program (presto_tpu/expr/params.py). The `dispatch` spans say how
many keys and how many aggregate calls each aggregation of a program
folds. Then what `benchmarks/datacheck.py`'s rule kinds cannot say of
the two flags Q1 groups by (clause 4.2.3, CURRENTDATE 1995-06-17), and
the persistent compile cache's threshold the package sets. CPU: counts
and answers, never rates."""

import datetime
import os
import sys

import numpy as np
import pytest

from presto_tpu.connectors import TpchConnector
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

DELTAS = [90, 60, 120, 61, 119, 77, 103, 68, 112]
#: far beyond clause 2.4.1.3's range, where the cut-off drops up to four
#: tenths of the rows and not 1-3% (still after CURRENTDATE, so that all
#: four groups stay: a group that goes takes its word out of the exchanged
#: dictionary, and a page with another dictionary is another trace of the
#: final program)
BEYOND = [150, 400, 1000]
GROUPS = [["A", "F"], ["N", "F"], ["N", "O"], ["R", "F"]]
CURRENTDATE = (datetime.date(1995, 6, 17) - datetime.date(1970, 1, 1)).days


@pytest.fixture(scope="module")
def connector():
    return TpchConnector(0.01)


@pytest.fixture(scope="module")
def served(connector):
    """(statement server's base, the cluster, the template, its reference
    over the connector's tables): one cluster for the module, so every
    case after the second runs the programs the first two built."""
    query = qgen.load_query("q01")
    reference = compare.load_reference(query)
    tables = bench_run.Tables(connector)
    cluster = TpuCluster(connector, n_workers=2)
    srv = StatementServer(cluster).start()
    try:
        yield srv.base, cluster, query, (
            lambda params: reference(tables, params))
    finally:
        srv.stop()
        cluster.stop()


def _ask(served, delta):
    base, _cluster, query, reference = served
    params = {"DELTA": delta}
    _cols, rows = run_statement(base, query["sql"].format(**params))
    return [list(r) for r in rows], reference(params)


@pytest.mark.parametrize("delta", DELTAS)
def test_q01_agrees_with_the_plain_reference(served, delta):
    got, want = _ask(served, delta)
    assert [r[:2] for r in want] == GROUPS      # the flags as words
    assert all(isinstance(r[9], int) and r[9] > 100 for r in want)
    gaps = compare.row_gaps(got, want)
    # the template's own limits: `count_order` and the words exact, the
    # seven float columns to 1e-9 (the float32 control reads 1e-8 and more)
    assert gaps["wrong_cells"] == 0 and gaps["max_rel_err"] <= 1e-9, (
        got, want)


def test_after_the_second_statement_no_delta_compiles(served):
    """Seven DELTAs after two, then three far beyond the clause's range:
    no miss of a worker's program cache, no request to the backend
    compiler (jax.monitoring, as tests/test_program_cache.py counts
    them), and another `count_order` for every DELTA: the input is live,
    not baked. And the spans say what each program folds: a worker's
    scan program a PARTIAL of two keys and eleven aggregate calls (the
    fragmenter splits each of the three `avg`s into a sum and a count of
    its own and shares neither with `sum(x)` or `count(*)`: 4 + 3 x 2 +
    1), the FINAL over the exchanged rows two keys and the statement's
    eight."""
    _base, cluster, _query, _reference = served
    counter = compile_counter()
    for delta in DELTAS[:2]:
        got, want = _ask(served, delta)
        assert compare.row_gaps(got, want)["max_rel_err"] <= 1e-9

    def kept():     # a miss makes a new Program in a worker's cache
        return {p for w in cluster.workers
                for p in w.task_manager.programs.jitted.values()}

    programs, requests = kept(), counter.requests
    counts = set()
    for delta in DELTAS[2:] + BEYOND:
        got, want = _ask(served, delta)
        assert compare.row_gaps(got, want) == {
            "wrong_cells": 0, "max_rel_err": pytest.approx(0, abs=1e-9)}
        counts.add(tuple(r[9] for r in got))
    assert len(counts) == len(DELTAS[2:]) + len(BEYOND)
    assert kept() == programs
    assert counter.requests == requests
    dispatched = [s.attributes for s in TRACER.get(cluster.last_trace_id)
                  if s.name == "dispatch"]
    assert not any(a["first_call"] for a in dispatched)
    scans = [a for a in dispatched if "TableScan" in a["operators"]]
    finals = [a for a in dispatched if "TableScan" not in a["operators"]]
    assert len(scans) == 2 and finals
    for a in scans:
        assert a["operators"].split("+")[0] == "Aggregation"
        assert (a["agg_steps"], a["group_keys"], a["aggregates"]) == (
            "PARTIAL", "2", "11")
        assert a["params"] == 4     # the folded DATE and the three 1s
    for a in finals:
        assert (a["agg_steps"], a["group_keys"], a["aggregates"]) == (
            "FINAL", "2", "8")
        assert a["params"] == 0
    for a in dispatched:
        assert ("group_keys" in a) == ("aggregates" in a) == (
            "agg_steps" in a)


def test_group_keys_and_aggregates_count_each_aggregation(connector):
    """One entry an AggregationNode, in `agg_steps`' order: an ungrouped
    aggregation has no key, and a program without one carries neither
    attribute."""
    from presto_tpu.exec.executor import Executor
    from presto_tpu.exec.engine import LocalEngine
    from presto_tpu.utils.tracing import trace_scope
    engine = LocalEngine(connector)
    with trace_scope("q01_agg_shapes", ""):
        Executor(connector).execute(engine.plan_sql(
            "select o_orderstatus, o_shippriority, o_orderpriority, "
            "min(o_totalprice), count(*) from orders group by 1, 2, 3"))
        Executor(connector).execute(engine.plan_sql(
            "select sum(o_totalprice), max(o_orderdate), count(*) "
            "from orders"))
        Executor(connector).execute(engine.plan_sql(
            "select o_orderkey from orders where o_totalprice < 1000"))
    seen = [(a.get("agg_steps"), a.get("group_keys"), a.get("aggregates"))
            for a in (s.attributes for s in TRACER.get("q01_agg_shapes")
                      if s.name == "dispatch")]
    assert ("SINGLE", "3", "2") in seen and ("SINGLE", "0", "3") in seen
    assert (None, None, None) in seen
    assert set(seen) <= {("SINGLE", "3", "2"), ("SINGLE", "0", "3"),
                         (None, None, None)}


# ---- the dates Q1 cuts, as clause 4.2.3 makes them -----------------------

def _day(y, m, d):
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


def test_order_dates_reach_enddate_less_151_days(connector):
    """O_ORDERDATE is uniform in STARTDATE .. ENDDATE - 151 days, so
    1992-01-01 .. 1998-08-02 with both ends drawn, and a line ships 1..121
    days later: up to 1998-12-01, the day Q1 counts DELTA back from. (To
    PR 34 the draw stopped 121 days short, the last line shipped on
    1998-08-01, and every DELTA of clause 2.4.1.3 kept every row.)"""
    t = connector.table("orders")
    odate = t.arrays["o_orderdate"][:int(t.num_rows)]
    assert (odate.min(), odate.max()) == (_day(1992, 1, 1), _day(1998, 8, 2))
    # uniform: the last 121 days hold their share of the orders
    share = (odate > _day(1998, 8, 2) - 121).mean()
    assert 0.8 * 121 / 2406 < share < 1.2 * 121 / 2406
    ship = _dates(connector, "l_shipdate")
    assert _day(1998, 12, 1) - 5 <= ship.max() <= _day(1998, 12, 1)


def test_q01_keeps_97_to_99_percent_and_delta_moves_the_answer(
        served, connector):
    """What defines the cell: at both ends of clause 2.4.1.3 the filter
    keeps 97-99% of lineitem (99.4% at DELTA 60, 97.3% at 120, as dbgen's
    data do), not all of it, and the answers differ."""
    n = int(connector.table("lineitem").num_rows)
    kept = {}
    for delta in (60, 120):
        got, want = _ask(served, delta)
        assert compare.row_gaps(got, want)["wrong_cells"] == 0
        kept[delta] = sum(r[9] for r in got)
    assert 0.97 * n < kept[120] < kept[60] < 0.995 * n
    cut = _day(1998, 12, 1)
    ship = _dates(connector, "l_shipdate")
    assert kept == {d: int((ship <= cut - d).sum()) for d in (60, 120)}


# ---- the two flags, as clause 4.2.3 makes them ---------------------------

def _words(connector, col):
    t = connector.table("lineitem")
    codes = t.arrays[col][:int(t.num_rows)]
    return np.asarray(t.dicts[col].words, dtype=object)[codes]


def _dates(connector, col):
    t = connector.table("lineitem")
    return t.arrays[col][:int(t.num_rows)]


def test_linestatus_is_o_exactly_where_the_line_ships_after_currentdate(
        connector):
    status = _words(connector, "l_linestatus")
    late = _dates(connector, "l_shipdate") > CURRENTDATE
    assert set(status) == {"O", "F"}
    assert ((status == "O") == late).all()
    assert 0.3 < late.mean() < 0.7      # both words well populated


def test_returnflag_is_n_exactly_where_the_line_arrives_after_currentdate(
        connector):
    flag = _words(connector, "l_returnflag")
    late = _dates(connector, "l_receiptdate") > CURRENTDATE
    assert set(flag) == {"R", "A", "N"}
    assert ((flag == "N") == late).all()
    assert set(flag[~late]) == {"R", "A"}
    share_r = (flag[~late] == "R").mean()
    assert 0.45 < share_r < 0.55        # R or A, evenly
    # so Q1 keeps four groups and never (R, O) or (A, O): a line received
    # by CURRENTDATE was shipped before it
    status = _words(connector, "l_linestatus")
    assert {(f, s) for f, s in zip(flag, status)} == {
        tuple(g) for g in GROUPS}


# ---- (a): the threshold is the documented constant ------------------------

def test_the_persistent_cache_keeps_every_program_over_a_second():
    """Whether a program is kept for the next process must follow from
    what it is, not from how long one compile of it happened to take: Q1's
    programs compile in 2.0-5.9 s on the chip, around the 5.0 s this used
    to be, and warm-up compiled 0, 2 or 3 of them by cache history. The
    package sets one second (`presto_tpu/__init__.py` says which programs
    sit where) and no size floor; nothing else in the package moves it."""
    import jax

    import presto_tpu
    assert presto_tpu.PERSISTENT_CACHE_MIN_COMPILE_SECS == 1.0
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
