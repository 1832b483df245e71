"""Literals as program inputs (presto_tpu/expr/params.py).

One `Program` object answers two literal sets, each rightly; what shapes
a program or a dictionary stays in the plan and so in the key (a string,
a NULL, a LIMIT, the length of an IN list, a literal a function reads
while it is traced); capacities learned under the shared key hold the
peak of the values seen; the host descales a decimal literal that meets
a double, bit for bit as `float(Decimal(text))`; and the guard a CPU can
hold for the TPU's low-rounding float64 division: the program lowered
for Q6's scan holds no division on float64 at all. CPU: counts and
answers, never rates."""

from decimal import Decimal

import jax
import numpy as np
import pytest

from presto_tpu.connectors import TpchConnector
from presto_tpu.data.column import Column, Page
from presto_tpu.exec.engine import LocalEngine
from presto_tpu.exec.program_cache import ProgramCache
from presto_tpu.exec.split_executor import SplitExecutor
from presto_tpu.expr.nodes import (
    Call, Form, InputRef, Literal, Param, SpecialForm,
)
from presto_tpu.expr.params import descale, lift_expr, lift_plan
from presto_tpu.ops.aggregate import AggSpec
from presto_tpu.plan.nodes import (
    AggregationNode, FilterNode, LimitNode, RemoteSourceNode,
)
from presto_tpu.types import (
    BIGINT, BOOLEAN, DATE, DOUBLE, VARCHAR, DecimalType,
)
from tests.tpch_queries import QUERIES

SF = 0.01
X = InputRef(0, BIGINT)


@pytest.fixture(scope="module")
def connector():
    return TpchConnector(SF)


@pytest.fixture(scope="module")
def engine(connector):
    return LocalEngine(connector)


def _kept(cache: ProgramCache) -> set:
    """The programs a cache holds. A miss makes a new `Program` (under a
    new key, or in place of another capacity variant), so an unchanged set
    is no miss — counted on the cache itself, not on the process's
    counter, which any other thread's dispatch moves."""
    return set(cache.jitted.values())


# ---- one program, many literals ------------------------------------------

def _sum_where(predicate, limit=None):
    """sum(x), count(*) over the remote rows that pass `predicate`."""
    node = RemoteSourceNode(("x",), (BIGINT,), node_id="7",
                            source_fragment_ids=("1",))
    node = FilterNode(("x",), (BIGINT,), source=node, predicate=predicate)
    if limit is not None:
        node = LimitNode(("x",), (BIGINT,), source=node, count=limit)
    return AggregationNode(
        ("s", "n"), (BIGINT, BIGINT), source=node,
        aggs=(AggSpec("sum", 0, BIGINT),
              AggSpec("count_star", None, BIGINT)))


def _between(lo: int, hi: int):
    return SpecialForm(Form.BETWEEN, (X, Literal(lo, BIGINT),
                                      Literal(hi, BIGINT)), BOOLEAN)


def _rows(connector, cache, plan, n=100):
    col = Column.from_numpy(np.arange(n, dtype=np.int64), BIGINT,
                            capacity=256)
    ex = SplitExecutor(connector, programs=cache)
    ex.set_remote_pages({"7": Page.from_columns([col], n, ("x",))})
    return ex.execute(plan).to_pylist()


def test_two_literal_sets_run_one_program_and_each_is_right(connector):
    cache = ProgramCache()
    assert _rows(connector, cache, _sum_where(_between(10, 19))) == [
        (sum(range(10, 20)), 10)]
    (program,) = cache.jitted.values()
    assert _rows(connector, cache, _sum_where(_between(40, 42))) == [
        (40 + 41 + 42, 3)]
    assert list(cache.jitted.values()) == [program]      # the same object
    # and the plan it is kept under holds places, not values
    (key,) = cache.jitted
    assert "Param(index=0" in repr(key[1]) and "Literal" not in repr(key[1])
    assert len(cache.learned) == 1


IN_12 = SpecialForm(Form.IN, (X, Literal(1, BIGINT), Literal(2, BIGINT)),
                    BOOLEAN)
IN_123 = SpecialForm(Form.IN, IN_12.args + (Literal(3, BIGINT),), BOOLEAN)
GT = Call("gt", (X, Literal(10, BIGINT)), BOOLEAN)
GT_NULL = Call("gt", (X, Literal(None, BIGINT)), BOOLEAN)


@pytest.mark.parametrize("first, second, rows", [
    (_sum_where(IN_12), _sum_where(IN_123), [(6, 3)]),
    (_sum_where(GT), _sum_where(GT_NULL), [(None, 0)]),
    (_sum_where(GT, limit=5), _sum_where(GT, limit=6),
     [(sum(range(11, 17)), 6)]),
], ids=["in-list-of-another-length", "null-literal", "limit"])
def test_what_shapes_a_program_stays_in_its_key(connector, first, second,
                                                rows):
    cache = ProgramCache()
    _rows(connector, cache, first)
    kept = _kept(cache)
    assert _rows(connector, cache, second) == rows
    assert len(cache.jitted) == 2 and len(_kept(cache) - kept) == 1
    assert lift_plan(first).plan != lift_plan(second).plan


def test_a_string_literal_stays_in_the_key_and_a_number_does_not(engine):
    """`c_mktsegment = '…'` is dictionary work at trace time; the balance
    beside it is an input."""
    sql = ("select count(*) from customer where c_mktsegment = '{}' "
           "and c_acctbal > {}")
    want = {}
    t = engine.connector.table("customer")
    n = int(t.num_rows)
    words = t.dicts["c_mktsegment"].words
    for seg, bal in (("BUILDING", 0), ("BUILDING", 5000),
                     ("MACHINERY", 5000)):
        codes = [i for i, w in enumerate(words) if w == seg]
        want[seg, bal] = int((np.isin(t.arrays["c_mktsegment"][:n], codes)
                              & (t.arrays["c_acctbal"][:n] > bal)).sum())
    programs = engine.executor.programs
    assert engine.execute_sql(sql.format("BUILDING", 0)) == [
        (want["BUILDING", 0],)]
    kept = _kept(programs)
    assert engine.execute_sql(sql.format("BUILDING", 5000)) == [
        (want["BUILDING", 5000],)]
    assert _kept(programs) == kept
    assert engine.execute_sql(sql.format("MACHINERY", 5000)) == [
        (want["MACHINERY", 5000],)]
    assert _kept(programs) > kept
    assert len(set(want.values())) == 3


S = InputRef(1, VARCHAR)


@pytest.mark.parametrize("expr, kept", [
    (Call("like", (S, Literal("a%", VARCHAR)), BOOLEAN), 1),
    (Call("substr", (S, Literal(2, BIGINT), Literal(3, BIGINT)), VARCHAR),
     2),
    (Call("round", (InputRef(2, DOUBLE), Literal(2, BIGINT)), DOUBLE), 1),
    (Call("date_trunc", (Literal("month", VARCHAR), InputRef(3, DATE)),
          DATE), 1),
    (Call("eq", (S, Literal("x", VARCHAR)), BOOLEAN), 1),
    (Call("gt", (X, Literal(None, BIGINT)), BOOLEAN), 1),
    (Call("gt", (InputRef(4, DecimalType(38, 2)),
                 Literal(5, DecimalType(38, 2))), BOOLEAN), 1),
], ids=["like-pattern", "substr-positions", "round-digits",
        "date_trunc-unit", "string", "null", "long-decimal"])
def test_a_literal_read_while_tracing_is_not_lifted(expr, kept):
    values = []
    assert lift_expr(expr, values) is expr and values == []
    assert sum(isinstance(a, Literal) for a in expr.args) == kept


def test_a_nested_expression_is_lifted_under_a_call_that_reads_literals():
    """round(x * 0.5, 2): the digits stay, the factor is an input."""
    inner = Call("multiply", (InputRef(2, DOUBLE), Literal(0.5, DOUBLE)),
                 DOUBLE)
    values = []
    out = lift_expr(Call("round", (inner, Literal(2, BIGINT)), DOUBLE),
                    values)
    assert out.args[0].args[1] == Param(0, DOUBLE)
    assert out.args[1] == Literal(2, BIGINT)
    assert [v.tolist() for v in values] == [0.5]


# ---- the spans say what was handed in ------------------------------------

def test_dispatch_says_how_many_literals_it_handed_in(connector):
    """Five to Q6's scan-filter-aggregate program, by type: two dates,
    the band's two edges as doubles, the quantity."""
    from presto_tpu.utils.tracing import TRACER, trace_scope
    engine = LocalEngine(connector)
    plan = engine.plan_sql(QUERIES[6])
    with trace_scope("params-q06", ""):
        engine.executor.execute(plan)
    (about,) = [s.attributes for s in TRACER.get("params-q06")
                if s.name == "dispatch"]
    assert about["params"] == 5 and about["first_call"]
    lifted = lift_plan(plan)
    assert [str(v.dtype) for v in lifted.values] == [
        "int32", "int32", "float64", "float64", "int64"]
    assert [v.tolist() for v in lifted.values] == [
        8766, 9131, 0.05, 0.07, 24]
    # a plan without a liftable literal keeps its identity, and says 0
    bare = engine.plan_sql("select max(n_nationkey) from nation")
    assert lift_plan(lift_plan(plan).plan).values == ()
    with trace_scope("params-bare", ""):
        engine.executor.execute(bare)
    assert [s.attributes["params"] for s in TRACER.get("params-bare")
            if s.name == "dispatch"] == [0]


def test_the_statements_own_nodes_are_what_the_stats_keep(connector):
    """The rebuilt, blanked nodes are the program's; row counts, history
    and dynamic-filter accounting find the statement's nodes, values
    and all."""
    from presto_tpu.config import Session
    engine = LocalEngine(connector)
    plan = engine.plan_sql(QUERIES[6])
    ex = engine.executor
    ex.session = Session({"collect_stats": "true"})
    ex.execute(plan)
    kept = [n for n, _cap in ex._node_map.values()]
    # the filter is fused into the aggregation above it, whose node
    # `lift_plan` rebuilt with everything between it and the root
    assert any(isinstance(n, AggregationNode) for n in kept)

    def walk(n):
        yield n
        for c in n.children():
            yield from walk(c)
    mine = {id(n) for n in walk(plan)}
    assert all(id(n) in mine for n in kept)
    assert "Param" not in repr(kept)


# ---- capacities under a shared key ---------------------------------------

JOINED = ("select o_orderdate, count(*) as n from orders, customer "
          "where o_custkey = c_custkey and o_orderdate < date '{}' "
          "group by o_orderdate")


def test_capacities_learned_under_one_key_hold_the_peak(connector,
                                                        tmp_path,
                                                        monkeypatch):
    """Q3's customer-orders join grouped by date (Q3 itself keeps under
    256 groups at SF0.01, the least capacity there is): a selective DATE
    anneals the aggregation's capacity to 256 slots; a less selective one
    needs some two thousand, overflows once and is re-lowered at the next
    bucket with every row; then the first DATE again lowers nothing, and
    loses nothing."""
    monkeypatch.setenv("PRESTO_TPU_CAPS_CACHE", str(tmp_path / "caps.json"))
    engine = LocalEngine(connector)
    dates = connector.table("orders").arrays["o_orderdate"]
    dates = dates[:int(connector.table("orders").num_rows)]

    def ask(day: str):
        rows = engine.execute_sql(JOINED.format(day))
        cutoff = (np.datetime64(day) - np.datetime64("1970-01-01")).astype(int)
        days, counts = np.unique(dates[dates < cutoff], return_counts=True)
        assert sorted(rows) == [(int(d), int(c))
                                for d, c in zip(days, counts)]
        return len(rows)

    def aggregation_capacity():
        return max(cap for caps in engine.executor.programs.learned.values()
                   for nid, cap in caps.items()
                   if not (isinstance(nid, int) and nid < 0))

    programs = engine.executor.programs
    assert ask("1992-01-20") < 30
    ask("1992-01-20")               # runs the annealed variants
    assert aggregation_capacity() == 256
    kept = _kept(programs)
    assert ask("1998-01-01") > 2000           # every group, none lost
    assert _kept(programs) != kept            # once: the next bucket
    assert aggregation_capacity() >= 2048
    grown, kept = aggregation_capacity(), _kept(programs)
    assert ask("1992-01-20") < 30
    assert ask("1998-01-01") > 2000
    assert _kept(programs) == kept and aggregation_capacity() == grown


# ---- the host descales ---------------------------------------------------

@pytest.mark.parametrize("hundredths", range(11))
def test_the_hosts_descale_is_the_nearest_double(hundredths):
    text = f"0.{hundredths:02d}"
    assert descale(hundredths, 2) == float(Decimal(text))
    values = []
    band = SpecialForm(Form.BETWEEN, (
        InputRef(0, DOUBLE), Literal(hundredths, DecimalType(4, 2)),
        Literal(hundredths + 2, DecimalType(4, 2))), BOOLEAN)
    out = lift_expr(band, values)
    assert out.args[1:] == (Param(0, DOUBLE), Param(1, DOUBLE))
    assert values[0].dtype == np.float64
    assert values[0].tolist() == float(Decimal(text))


@pytest.mark.parametrize("text", [
    "0.01", "123456.78", "9999999999.99", "-0.07", "104950.00",
    "793422.05", "0.10", "4503599627.37"])
def test_a_decimal_12_2_descales_bit_for_bit(text):
    unscaled = int(Decimal(text).scaleb(2))
    lit = Literal(unscaled, DecimalType(12, 2))
    for expr in (Call("lt", (InputRef(0, DOUBLE), lit), BOOLEAN),
                 Call("multiply", (lit, InputRef(0, DOUBLE)), DOUBLE),
                 Call("cast", (lit,), DOUBLE)):
        values = []
        lift_expr(expr, values)
        assert [v.tolist().hex() for v in values] == [
            float(Decimal(text)).hex()]
    # against a decimal column it stays the exact, unscaled integer
    values = []
    lift_expr(Call("lt", (InputRef(0, DecimalType(12, 2)), lit), BOOLEAN),
              values)
    assert values[0].dtype == np.int64 and values[0].tolist() == unscaled


def _f64_divisions(jaxpr) -> int:
    """`div` equations with a float64 operand, sub-jaxprs included."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "div" and any(
                getattr(v.aval, "dtype", None) == np.float64
                for v in eqn.invars):
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _f64_divisions(sub)
    return found


def test_q06s_scan_program_divides_nothing_in_float64(engine):
    """What a CPU can hold of the TPU fault: emulated float64 division
    rounds low, so `5 / 100` on the device is under 0.05 and the band
    lost its edge. The lowered program takes the band's edges as doubles
    the host made. Lowered with the literals left in (as every program
    was before), the same plan divides twice: the check can see."""
    ex = engine.executor
    plan = engine.plan_sql(QUERIES[6])
    lifted = lift_plan(plan)
    for tree, values, divisions in ((lifted.plan, lifted.values, 0),
                                    (plan, (), 2)):
        run, scans, _watch = ex._lower(tree, {})
        pages = [ex._fetch(s) for s in scans]
        jaxpr = jax.make_jaxpr(run)(pages, values)
        assert _f64_divisions(jaxpr.jaxpr) == divisions
