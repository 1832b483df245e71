"""TPC-DS query subset vs a sqlite oracle over the same generated data
(the TPC-H suite's strategy applied to the second fixture connector;
reference: presto-tpcds + benchto tpcds.yaml, SURVEY.md §6)."""

import math
import sqlite3

import pytest

from presto_tpu.connectors import TpcdsConnector
from presto_tpu.exec import LocalEngine
from tests.oracle import table_df
from tests.test_tpch_full import _iso, to_sqlite
from tests.tpcds_queries import (
    Q22_SQLITE, Q27_SQLITE, QUERIES, SQLITE_OVERRIDES,
)

SF = 0.002

_TABLES = ["date_dim", "time_dim", "item", "store", "warehouse",
           "promotion", "customer", "customer_address",
           "customer_demographics", "household_demographics",
           "store_sales", "catalog_sales", "web_sales", "inventory",
           "store_returns", "catalog_returns", "web_returns",
           "reason", "ship_mode", "income_band", "web_page",
           "web_site", "call_center", "catalog_page"]


@pytest.fixture(scope="module")
def engine():
    return LocalEngine(TpcdsConnector(SF))


@pytest.fixture(autouse=True)
def _drop_compile_caches(engine):
    """Many distinct query programs in one process starve the XLA CPU
    compiler (observed segfaults — same workaround as the distributed
    TPC-H suite)."""
    yield
    import jax
    engine.executor.programs.clear()
    jax.clear_caches()


@pytest.fixture(scope="module")
def oracle():
    conn = TpcdsConnector(SF)
    db = sqlite3.connect(":memory:")
    # sqlite's math functions are a compile-time option (-DSQLITE_ENABLE_MATH
    # _FUNCTIONS) absent from some builds; the oracle must not depend on it
    db.create_function(
        "sqrt", 1, lambda x: None if x is None else math.sqrt(x))
    for t in _TABLES:
        df = table_df(conn, t)
        for col, typ in conn.schema(t):
            if typ.name == "date":
                df[col] = df[col].map(_iso)
        db.execute(f"create table {t} ({', '.join(df.columns)})")
        db.executemany(
            f"insert into {t} values ({', '.join('?' * len(df.columns))})",
            df.itertuples(index=False, name=None))
    db.commit()
    return db


def run_case(qnum, engine, oracle):
    sql = QUERIES[qnum]
    got = engine.execute_sql(sql)
    types = engine.plan_sql(sql).output_types
    got = [tuple(_iso(v) if t.name == "date" and v is not None else v
                 for v, t in zip(row, types)) for row in got]
    exp_sql = to_sqlite(
        {22: Q22_SQLITE, 27: Q27_SQLITE, **SQLITE_OVERRIDES}
        .get(qnum) or sql)
    exp = oracle.execute(exp_sql).fetchall()

    # floats sort ROUNDED so epsilon differences (summation order) can't
    # mis-pair otherwise-identical rows between the two engines
    key = lambda r: tuple(                            # noqa: E731
        (v is None, round(v, 3) if isinstance(v, float) else v)
        for v in r)
    got_s, exp_s = sorted(got, key=key), sorted(exp, key=key)
    assert len(got_s) == len(exp_s), \
        f"Q{qnum}: {len(got_s)} rows != {len(exp_s)}\n" \
        f"got[:3]={got_s[:3]}\nexp[:3]={exp_s[:3]}"
    for i, (g, e) in enumerate(zip(got_s, exp_s)):
        for j, (x, y) in enumerate(zip(g, e)):
            if x is None or y is None:
                assert x is None and y is None, \
                    f"Q{qnum} row {i} col {j}: {x!r} != {y!r}"
            elif isinstance(x, float) or isinstance(y, float):
                rel = max(abs(float(y)), 1.0)
                assert abs(float(x) - float(y)) <= 1e-6 * rel, \
                    f"Q{qnum} row {i} col {j}: {x!r} != {y!r}"
            else:
                assert x == y, f"Q{qnum} row {i} col {j}: {x!r} != {y!r}"


# Tier-1 runs under xdist's `--dist loadfile`: a file is one worker's. All
# 99 queries in this file were 1,381 s of a 1,394 s run against a limit of
# 1,470 s, whatever the other five workers did, so the even-numbered ones
# run from `test_tpcds_even.py` (same cases, same fixtures, own worker).
@pytest.mark.parametrize("qnum", [q for q in sorted(QUERIES) if q % 2])
def test_tpcds(qnum, engine, oracle):
    run_case(qnum, engine, oracle)


def test_tpcds_distributed(oracle):
    """A TPC-DS star join + a ROLLUP through the fragmenter on the
    8-device mesh."""
    from presto_tpu.exec.dist_executor import DistEngine
    from presto_tpu.parallel import device_mesh

    eng = DistEngine(TpcdsConnector(SF), device_mesh(8))
    for qnum in (55, 22):
        run_case(qnum, eng, oracle)
