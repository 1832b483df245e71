"""The served path (two workers, HTTP pages, `POST /v1/statement`) keeps
a split scan's columns on the device: after the statements that build a
template's programs, a statement with literals no statement had before
moves no byte of lineitem, misses no program and answers right, by the
benchmark's plain references (`benchmarks/queries/q06.py`, `q01.py`) at
SF0.01. CPU: counts, bytes and answers, never rates."""

import os
import sys

import pytest

from presto_tpu.connectors import TpchConnector
from presto_tpu.obs.metrics import REGISTRY
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

#: three statements a template, each with literals of its own; the third
#: is the one held to "nothing moves"
STATEMENTS = {
    "q06": [{"DATE": "1994-01-01", "DISCOUNT": "0.06", "QUANTITY": 24},
            {"DATE": "1996-01-01", "DISCOUNT": "0.03", "QUANTITY": 25},
            {"DATE": "1993-01-01", "DISCOUNT": "0.08", "QUANTITY": 24}],
    "q01": [{"DELTA": 90}, {"DELTA": 61}, {"DELTA": 118}],
}


@pytest.fixture(scope="module")
def served():
    connector = TpchConnector(0.01)
    cluster = TpuCluster(connector, n_workers=2)
    srv = StatementServer(cluster).start()
    try:
        yield srv.base, cluster, bench_run.Tables(connector)
    finally:
        srv.stop()
        cluster.stop()


@pytest.mark.parametrize("template", sorted(STATEMENTS))
def test_the_third_statement_moves_no_byte_of_lineitem(served, template):
    base, cluster, tables = served
    query = qgen.load_query(template)
    reference = compare.load_reference(query)
    scans = REGISTRY.get("presto_tpu_scan_cache_total")
    answers = []
    for params in STATEMENTS[template]:
        misses = scans.value(result="miss")
        hits = scans.value(result="hit")
        _cols, rows = run_statement(base, query["sql"].format(**params))
        got = [list(r) for r in rows]
        gaps = compare.row_gaps(got, reference(tables, params))
        assert gaps["wrong_cells"] == 0 and gaps["max_rel_err"] <= 1e-9
        answers.append(got)
    assert answers[2] not in answers[:2]            # the literals are live
    spans = TRACER.get(cluster.last_trace_id)
    uploads = [s.attributes for s in spans if s.name == "upload"
               and s.attributes.get("table") == "lineitem"]
    columns = len(query["reads"]["lineitem"])
    # one scan a worker: every column and the row count were resident
    assert len(uploads) == 2
    assert sum(u["bytes"] for u in uploads) == 0
    assert all(u["resident"] > 10_000 * columns for u in uploads)
    assert scans.value(result="miss") == misses
    assert scans.value(result="hit") == hits + 2 * columns
    dispatched = [s.attributes for s in spans if s.name == "dispatch"]
    assert dispatched and not any(a["first_call"] for a in dispatched)
