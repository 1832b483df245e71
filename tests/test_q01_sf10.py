"""TPC-H Q1 at SF10 (`q01_sf10_serial`): its plain reference
(`benchmarks/queries/q01_sf10.py`: one pass to sums by flag, status and
ship day, then each DELTA adds its days) against `queries/q01.py`, its
float32 branch against the template's limit, its files against Q1's, and
the cell run end to end through `POST /v1/statement` at SF0.01. CPU:
answers and counts, never rates."""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare  # noqa: E402
import qgen  # noqa: E402
import run as bench_run  # noqa: E402

DELTAS = list(range(60, 121))


@pytest.fixture(scope="module")
def tables():
    return bench_run.Tables(bench_run.make_connector("tpch", 0.01))


@pytest.fixture(scope="module")
def references():
    return (compare.load_reference(qgen.load_query("q01")),
            compare.load_reference(qgen.load_query("q01_sf10")))


@pytest.mark.parametrize("delta", DELTAS)
def test_the_day_sums_answer_as_the_filter_does(tables, references, delta):
    plain, by_day = references
    want = plain(tables, {"DELTA": delta})
    got = by_day(tables, {"DELTA": delta})
    assert [r[:2] for r in got] == [r[:2] for r in want]   # the words
    assert [r[9] for r in got] == [r[9] for r in want]     # count_order
    gaps = compare.row_gaps(got, want)
    assert gaps["wrong_cells"] == 0 and gaps["max_rel_err"] <= 1e-12


def test_the_float32_branch_breaks_the_limit(tables, references):
    plain, by_day = references
    limit = qgen.load_query("q01_sf10")["limits"]["max_rel_err"]
    worst = max(compare.row_gaps(by_day(tables, {"DELTA": d}, np.float32),
                                 plain(tables, {"DELTA": d}))["max_rel_err"]
                for d in (60, 90, 120))
    assert worst > 10 * limit
    assert set(tables.__dict__["_q01_day_sums"]) == {"float64", "float32"}


def test_the_template_is_q01_but_for_its_name():
    mine, q01 = qgen.load_query("q01_sf10"), qgen.load_query("q01")
    assert mine.pop("name") == "q01_sf10" and q01.pop("name") == "q01"
    assert mine == q01


def test_the_configuration_is_q01s_at_sf10():
    _b, cell, mine, traffic, queries = bench_run.load_cell("q01_sf10_serial")
    theirs = bench_run.load_cell("q01_serial")[2]
    assert cell["config"] == mine["name"] == "tpch_sf10_http_q01"
    assert cell["chips"] == mine["chips"] == 1
    assert traffic["cycle"] == ["q01_sf10"] and traffic["clients"] == 1
    assert traffic["loop"] == "closed" and traffic["trace_seconds"] == 20
    own = {"name", "source", "deployment", "connector", "scale_factor",
           "rows", "reduced_why"}
    assert set(mine) == set(theirs)
    for key in set(mine) - own:
        assert mine[key] == theirs[key], key
    assert (mine["connector"], theirs["connector"]) == (
        "tpch_text_on_read", "tpch")
    assert mine["scale_factor"] == 10.0 and mine["reduced"] == [
        "scale_factor"]
    assert mine["source"].endswith("scale factor 10")
    assert mine["rows"]["orders"] == 15_000_000
    assert mine["rows"]["customer"] == 1_500_000
    assert queries["q01_sf10"]["reads"] == {"lineitem": [
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"]}


def test_the_connector_is_tpch_under_a_name_of_its_own():
    """The name selects the generator that leaves text columns for their
    first read; tables, splits and connector id are TPC-H's."""
    from presto_tpu.connectors import TpchConnector
    mine = bench_run.make_connector("tpch_text_on_read", 0.001)
    tpch = bench_run.make_connector("tpch", 0.001)
    assert isinstance(mine, TpchConnector) and mine.scale_factor == 0.001
    assert mine.connector_id("lineitem") == tpch.connector_id() == "tpch"
    assert mine.table_splits("lineitem", 2) == tpch.table_splits(
        "lineitem", 2)
    assert mine.table("lineitem") is tpch.table("lineitem")


def test_a_program_without_it_stops_before_generating(monkeypatch):
    """Where the connector lacks the name (an eager generator, whose SF10
    lineitem does not fit the host), the cell exits at once."""
    import presto_tpu.connectors as connectors
    monkeypatch.setattr(connectors, "__all__", [
        n for n in connectors.__all__ if n != "TpchTextOnReadConnector"])
    with pytest.raises(SystemExit, match="tpch_text_on_read"):
        bench_run.make_connector("tpch_text_on_read", 10.0)


def test_the_lineitem_rows_are_the_connectors_at_sf10():
    """Lines an order are drawn 1..7, so lineitem's count is the sum of
    that draw, made where the generator makes it; its table at SF10 is
    not generated here."""
    from presto_tpu.connectors import tpch
    sf = 10.0
    c = tpch._counts(sf)
    rng = np.random.default_rng(tpch._seed("orders", sf, 0))
    rng.integers(1, c["customer"] + 1, size=c["orders"])     # o_custkey
    rng.integers(tpch._MIN_DATE, tpch._MAX_ORDER_DATE - 121,
                 size=c["orders"])                          # the old draw
    lines = int(rng.integers(1, 8, size=c["orders"]).sum())
    config = qgen.load_json("configs", "tpch_sf10_http_q01.json")
    assert config["rows"]["lineitem"] == lines == 60_012_984
    # the same prefix at SF1 gives what every SF1 configuration says
    assert qgen.load_json("configs", "tpch_sf1_http_q01.json")["rows"][
        "lineitem"] == 6_001_917


def test_the_cell_is_correct_through_the_statement_protocol(
        capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("PRESTO_TPU_CAPS_CACHE", str(tmp_path / "caps.json"))
    rc = bench_run.main(["--workload", "q01_sf10_serial", "--seed",
                         "3141592653", "--seconds", "2", "--trace", "0",
                         "--allow-cpu", "--sf", "0.01"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["run"]["workload"] == "q01_sf10_serial"
    assert result["run"]["compiled_in_window"] == 0
    compared = result["compared"]
    assert compared["q01_sf10.wrong_cells"]["value"] == 0
    assert compared["q01_sf10.max_rel_err"]["value"] <= 1e-12
    assert {"qph", "wall_p50_s", "setup_s"} <= set(result["metrics"])
