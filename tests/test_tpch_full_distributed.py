"""All 22 TPC-H queries executed DISTRIBUTED on the 8-device CPU mesh,
checked against the same sqlite oracle as the single-device suite.

This is the round-2 acceptance gate from VERDICT.md #1: the fragmenter
(plan/fragment.add_exchanges) + DistExecutor lower every SQL plan onto the
mesh — sharded scans, partial/final aggregation around hash exchanges,
co-partitioned and broadcast joins — and the results must match sqlite
row-for-row. Reference analogue: re-running AbstractTestQueries under
DistributedQueryRunner (SURVEY.md §4)."""

import pytest

from presto_tpu.connectors import TpchConnector
from presto_tpu.exec.dist_executor import DistEngine
from presto_tpu.parallel import device_mesh
from tests.test_tpch_full import SF, oracle, run_case  # noqa: F401
from tests.tpch_queries import QUERIES

NDEV = 8


@pytest.fixture(scope="module")
def engine():
    return DistEngine(TpchConnector(SF), device_mesh(NDEV))

@pytest.fixture(autouse=True)
def _drop_compile_caches(engine):
    """Each distributed query compiles several fragment programs; keeping
    22 queries' worth of XLA CPU executables live in one process starves
    the compiler (observed segfaults partway through the suite). Queries
    don't re-execute each other's plans here, so drop everything."""
    yield
    import jax
    engine.executor.programs.clear()
    jax.clear_caches()


@pytest.mark.parametrize("qnum", sorted(QUERIES))
def test_tpch_distributed(qnum, engine, oracle):  # noqa: F811
    run_case(qnum, engine, oracle)


def test_distributed_order_by_row_identical(engine):
    """VERDICT.md #7: distributed ORDER BY (range exchange + local sorts)
    must produce row-identical ordered output — device order is global
    order, no gather-then-sort on one device."""
    from tests.test_tpch_full import SF as _SF
    from presto_tpu.exec import LocalEngine

    local = LocalEngine(TpchConnector(_SF))
    for q in (
        "select c_custkey, c_acctbal from customer "
        "order by c_acctbal desc, c_custkey",
        "select o_orderdate, count(*) from orders group by o_orderdate "
        "order by o_orderdate",
    ):
        assert engine.execute_sql(q) == local.execute_sql(q)
