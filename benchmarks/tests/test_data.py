"""The generated tables against what the configuration's source says of
them (datacheck.py), at SF0.01: the connector as it stands breaks no rule,
and a generator's fault that `correct` could not see (its reference reads
the same arrays) breaks one. Numpy over the connector's arrays, no cluster.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_data.py -q
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import datacheck  # noqa: E402
import qgen  # noqa: E402
import run as bench_run  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CONFIGS = [c["name"] for c in json.load(_f)["configs"]]
SF = 0.01


class Broken:
    """The tables with one column, or one dictionary, replaced."""

    def __init__(self, tables, column=None, words=None):
        self._t, self._column, self._words = tables, column or {}, words or {}

    def column(self, table, col):
        got = self._t.column(table, col)
        fault = self._column.get((table, col))
        return fault(got) if fault else got

    def words(self, table, col):
        return self._words.get((table, col)) or self._t.words(table, col)


@pytest.fixture(scope="module", params=CONFIGS)
def checked(request):
    config = qgen.load_json("configs", request.param + ".json")
    conn = bench_run.make_connector(config["connector"], SF)
    return bench_run.Tables(conn), config, SF / config["scale_factor"]


def test_the_connectors_tables_break_no_rule(checked):
    tables, config, scale = checked
    assert datacheck.faults(tables, config, scale) == []


def shifted(by):
    return lambda v: v + by


FAULTS = {
    "a dictionary miscoded": dict(words={("customer", "c_mktsegment"): [
        "AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLDS"]}),
    "a population too wide": dict(column={
        ("lineitem", "l_discount"): lambda v: np.where(v == 0.10, 0.11, v)}),
    "rows lost": dict(column={
        ("orders", "o_orderkey"): lambda v: v[:-1]}),
    "a child before its parent": dict(column={
        ("lineitem", "l_shipdate"): lambda v: np.where(
            np.arange(len(v)) == 5, v - 200, v)}),
    "a key twice": dict(column={
        ("customer", "c_custkey"): lambda v: np.r_[v[:-1], v[0]]}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_generators_fault_breaks_a_rule(checked, fault):
    tables, config, scale = checked
    if config["connector"] != "tpch":
        pytest.skip("the planted faults name TPC-H columns")
    assert datacheck.faults(Broken(tables, **FAULTS[fault]), config, scale)


def test_the_command_says_what_it_found(capsys):
    rc = datacheck.main(["--config", CONFIGS[0], "--sf", str(SF)])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["rules_broken"] == 0 and last["sf"] == SF
