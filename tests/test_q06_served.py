"""TPC-H Q6 with the substitution parameters of clause 2.4.6.3 on the
served path (two workers, HTTP pages, `POST /v1/statement`) against the
benchmark's plain reference (`benchmarks/queries/q06.py`: numpy over the
connector's arrays, the discount band decided in whole hundredths,
nothing of the engine), at SF0.01.

Every DISCOUNT of the clause's eight, every DATE of its five and both
QUANTITYs; and after a worker's first statement of the shape no other
triple misses its program cache or asks the compiler for anything: the
literals are inputs of one program (presto_tpu/expr/params.py), and the
decimal ones reach it as the doubles the host made of them. CPU: counts
and answers, never rates."""

import os
import sys

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

DISCOUNTS = [f"0.0{d}" for d in range(2, 10)]
DATES = [f"{y}-01-01" for y in range(1993, 1998)]
TRIPLES = (
    [{"DATE": "1995-01-01", "DISCOUNT": d, "QUANTITY": 25}
     for d in DISCOUNTS]
    + [{"DATE": d, "DISCOUNT": "0.03", "QUANTITY": 24} for d in DATES]
    + [{"DATE": "1996-01-01", "DISCOUNT": "0.08", "QUANTITY": q}
       for q in (24, 25)])


def _id(p) -> str:
    return f"{p['DATE'][:4]}-{p['DISCOUNT']}-{p['QUANTITY']}"


@pytest.fixture(scope="module")
def served():
    """(statement server's base, the cluster, the template, its reference
    over the connector's tables): one cluster for the module, so every
    case after the first runs the first one's programs."""
    connector = TpchConnector(0.01)
    query = qgen.load_query("q06")
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


def _ask(served, params):
    base, _cluster, query, reference = served
    _cols, rows = run_statement(base, query["sql"].format(**params))
    return [list(r) for r in rows], reference(params)


@pytest.mark.parametrize("params", TRIPLES, ids=_id)
def test_q06_agrees_with_the_plain_reference(served, params):
    got, want = _ask(served, params)
    assert want[0][0] is not None and want[0][0] > 0
    gaps = compare.row_gaps(got, want)
    # the template's own limits: the float32 control reads 1e-7 and more
    assert gaps["wrong_cells"] == 0 and gaps["max_rel_err"] <= 1e-9, (
        got, want)


def test_after_the_first_statement_no_triple_compiles(served):
    """The other fourteen triples after one: no miss of a worker's
    program cache, no request to the backend compiler (jax.monitoring,
    as tests/test_program_cache.py counts them), and the spans say what
    was handed in: five literals to each worker's scan program, none to
    the final aggregation."""
    _base, cluster, _query, _reference = served
    counter = compile_counter()
    got, want = _ask(served, TRIPLES[0])
    assert compare.row_gaps(got, want)["max_rel_err"] <= 1e-9

    def kept():     # a miss makes a new Program in a worker's cache
        return {p for w in cluster.workers
                for p in w.task_manager.programs.jitted.values()}

    programs, requests = kept(), counter.requests
    answers = set()
    for params in TRIPLES[1:]:
        got, want = _ask(served, params)
        assert compare.row_gaps(got, want) == {
            "wrong_cells": 0, "max_rel_err": pytest.approx(0, abs=1e-9)}
        answers.add(got[0][0])
    assert len(answers) == len(TRIPLES) - 1     # one program, 14 answers
    assert kept() == programs and len(programs) == 3
    assert counter.requests == requests
    dispatched = [s.attributes for s in TRACER.get(cluster.last_trace_id)
                  if s.name == "dispatch"]
    assert not any(a["first_call"] for a in dispatched)
    scans = [a for a in dispatched if "TableScan" in a["operators"]]
    finals = [a for a in dispatched if "TableScan" not in a["operators"]]
    assert [a["params"] for a in scans] == [5, 5]
    assert finals and all(a["params"] == 0 for a in finals)
