"""A program outlives the task that first ran it (exec/program_cache.py).

The worker owns the jitted programs and hands its cache to every task's
executor: a second task of a fragment traces, lowers and compiles
nothing; the key carries everything `_lower` read, so a page of another
capacity is another program; a kept program holds nothing of the task
that made it; two tasks that reach a new key together trace it once.
And a string dictionary made by a fuse is the same object when its words
are the same, or `jax.jit`'s own cache would miss where the worker's
hits. CPU: counts, never rates."""

import gc
import os
import sys
import threading
import time
import weakref

import numpy as np
import pytest

from presto_tpu.connectors import TpchConnector
from presto_tpu.data.column import (
    Column, Page, StringDict, concat_pages_host,
)
from presto_tpu.exec import executor as executor_mod
from presto_tpu.exec.program_cache import _PROGRAMS, ProgramCache
from presto_tpu.exec.split_executor import SplitExecutor
from presto_tpu.ops.aggregate import AggSpec
from presto_tpu.plan.nodes import (
    AggregationNode, FilterNode, RemoteSourceNode,
)
from presto_tpu.expr.nodes import Call, InputRef, Literal
from presto_tpu.server.task_manager import TpuTaskManager
from presto_tpu.types import BIGINT, BOOLEAN, VARCHAR
from presto_tpu.utils.tracing import TRACER, TraceContext
from tests.protocol_fixtures import q6_fragment, task_update_request

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from compile_counter import compile_counter  # noqa: E402

SF = 0.01


@pytest.fixture(scope="module")
def connector():
    return TpchConnector(SF)


def _misses() -> float:
    return _PROGRAMS.value(result="miss")


def _hits() -> float:
    return _PROGRAMS.value(result="hit")


# ---- two tasks of one fragment on one worker -----------------------------

def _run_task(tm, task_id, trace_id):
    tur = task_update_request(q6_fragment(SF), n_splits=2, sf=SF)
    tm.create_or_update(task_id, tur, TraceContext(trace_id))
    task = tm.get(task_id)
    for _ in range(1200):
        if task.state in ("FINISHED", "FAILED", "ABORTED"):
            break
        time.sleep(0.05)
    assert task.state == "FINISHED", task.failures
    return [s.attributes for s in TRACER.get(trace_id)
            if s.name == "dispatch"]


def test_a_second_task_of_a_fragment_compiles_nothing(connector):
    tm = TpuTaskManager(connector)
    counter = compile_counter()
    first = _run_task(tm, "pc.0.0.0.0", "program-cache-task-1")
    assert first and first[0]["first_call"]
    kept = len(tm.programs.jitted)
    requests, misses = counter.requests, _misses()
    second = _run_task(tm, "pc.0.0.1.0", "program-cache-task-2")
    # the same lifespans, each a dispatch of a program the worker has
    assert len(second) == len(first)
    assert not any(a["first_call"] for a in second)
    assert [a["program"] for a in second] == [a["program"] for a in first]
    assert counter.requests == requests and _misses() == misses
    assert len(tm.programs.jitted) == kept
    # another worker is another cache
    other = TpuTaskManager(connector)
    assert other.programs is not tm.programs
    assert _run_task(other, "pc.0.0.2.0",
                     "program-cache-task-3")[0]["first_call"]


# ---- the key, and what a kept program holds ------------------------------

def _sum_over_remote(threshold: int = 10, op: str = "gt"):
    """sum(x), count(*) over the remote rows with x > threshold (or
    x >= threshold: another plan, where another threshold is the same
    plan with another input)."""
    remote = RemoteSourceNode(("x",), (BIGINT,), node_id="7",
                              source_fragment_ids=("1",))
    keep = FilterNode(("x",), (BIGINT,), source=remote, predicate=Call(
        op, (InputRef(0, BIGINT), Literal(threshold, BIGINT)), BOOLEAN))
    return AggregationNode(
        ("s", "n"), (BIGINT, BIGINT), source=keep,
        aggs=(AggSpec("sum", 0, BIGINT),
              AggSpec("count_star", None, BIGINT)))


def _remote_page(n: int, capacity: int) -> Page:
    col = Column.from_numpy(np.arange(n, dtype=np.int64), BIGINT,
                            capacity=capacity)
    return Page.from_columns([col], n, ("x",))


def _execute(connector, cache, plan, page):
    ex = SplitExecutor(connector, programs=cache)
    ex.set_remote_pages({"7": page})
    return ex, ex.execute(plan).to_pylist()


def test_a_page_of_another_capacity_is_another_program(connector):
    cache, plan = ProgramCache(), _sum_over_remote()
    hits, misses = _hits(), _misses()
    ex, rows = _execute(connector, cache, plan, _remote_page(100, 256))
    assert rows == [(sum(range(11, 100)), 89)]
    assert (_hits() - hits, _misses() - misses) == (0, 1)
    # a plan without a subquery keeps its identity through execute(): a
    # task that executes it once a chunk then finds its islands, and
    # their stats ids, where the first chunk left them
    assert ex._resolve_subqueries(plan) is plan
    # the same plan and capacities over 1024 slots: the closure of the
    # 256-slot program holds integers derived from 256, so it must miss
    _ex, rows = _execute(connector, cache, plan, _remote_page(1000, 1024))
    assert rows == [(sum(range(11, 1000)), 989)]
    assert (_hits() - hits, _misses() - misses) == (0, 2)
    # and the first shape again, from a third executor: a hit
    _ex, rows = _execute(connector, cache, plan, _remote_page(50, 256))
    assert rows == [(sum(range(11, 50)), 39)]
    assert (_hits() - hits, _misses() - misses) == (1, 2)
    assert len(cache.jitted) == 2


def test_a_kept_program_holds_nothing_of_its_first_task(connector):
    cache, plan = ProgramCache(), _sum_over_remote()
    page = _remote_page(100, 256)
    ex, rows = _execute(connector, cache, plan, page)
    assert rows == [(sum(range(11, 100)), 89)]
    gone = [weakref.ref(ex), weakref.ref(page)]
    del ex, page
    gc.collect()
    assert [r() for r in gone] == [None, None]
    assert len(cache.jitted) == 1
    misses = _misses()
    _ex, rows = _execute(connector, cache, plan, _remote_page(20, 256))
    assert rows == [(sum(range(11, 20)), 9)] and _misses() == misses


def test_tasks_reaching_a_new_key_together_trace_it_once(
        connector, monkeypatch):
    """More threads than cores, each a task's executor over a page of
    its own, all at one new key (twice: the learned capacities are shared
    state too). A second trace of the closure, or a capacity lost between
    a copy and its fold, would show as a count or as a wrong sum."""
    traces = []
    aggregate = executor_mod.grouped_aggregate

    def counted(*args, **kwargs):
        traces.append(threading.get_ident())
        time.sleep(0.3)      # long enough for the other threads to arrive
        return aggregate(*args, **kwargs)

    monkeypatch.setattr(executor_mod, "grouped_aggregate", counted)
    cache = ProgramCache()
    sizes = [20 + 7 * i for i in range((os.cpu_count() or 4) + 4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for round_, (threshold, op) in enumerate(((3, "gt"), (4, "ge"))):
            plan = _sum_over_remote(threshold, op)
            threshold -= op == "ge"        # x >= 4 keeps what x > 3 does
            start = threading.Barrier(len(sizes))
            rows = {}

            def task(n, plan=plan, start=start, rows=rows):
                start.wait(timeout=60)
                rows[n] = _execute(connector, cache, plan,
                                   _remote_page(n, 256))[1]

            threads = [threading.Thread(target=task, args=(n,))
                       for n in sizes]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            assert rows == {
                n: [(sum(range(threshold + 1, n)), n - threshold - 1)]
                for n in sizes}
            assert len(traces) == round_ + 1
            assert len(cache.jitted) == len(cache.learned) == round_ + 1
    finally:
        sys.setswitchinterval(interval)


def test_the_cache_evicts_the_least_recently_used_at_its_bound(connector):
    cache, plan = ProgramCache(entries=2), _sum_over_remote()
    for capacity in (256, 512, 1024):
        _execute(connector, cache, plan, _remote_page(30, capacity))
    assert len(cache.jitted) == 2
    misses = _misses()
    _execute(connector, cache, plan, _remote_page(30, 1024))   # kept
    assert _misses() == misses
    _execute(connector, cache, plan, _remote_page(30, 256))    # evicted
    assert _misses() == misses + 1 and len(cache.jitted) == 2
    # plans with learned capacities are bounded apart, the same way
    # (another comparison is another plan; another threshold is not)
    for op in ("gt", "ge", "lt"):
        _execute(connector, cache, _sum_over_remote(1, op),
                 _remote_page(30, 256))
    assert len(cache.learned) == 2 and len(cache.jitted) == 2


def test_a_capacity_variant_replaces_the_one_before_it(connector):
    """The first execution lowers at the planner's guess, annealing
    brings the groups' capacity down, and the second lowers again: the
    first variant is not asked for any more and must not stay loaded."""
    remote = RemoteSourceNode(("x",), (BIGINT,), node_id="7",
                              source_fragment_ids=("1",))
    plan = AggregationNode(
        ("x", "n"), (BIGINT, BIGINT), source=remote, group_fields=(0,),
        aggs=(AggSpec("count_star", None, BIGINT),))
    cache = ProgramCache()
    capacities, misses = [], _misses()
    for _ in range(3):
        ex = SplitExecutor(connector, programs=cache)
        ex.set_remote_pages({"7": _remote_page(30, 4096)})
        out = ex.execute(plan)
        assert sorted(out.to_pylist()) == [(i, 1) for i in range(30)]
        capacities.append(out.capacity)
    assert capacities[0] > capacities[1] == capacities[2] == 256
    assert _misses() - misses == 2 and len(cache.jitted) == 1
    (program,) = cache.jitted.values()
    assert dict(program.caps) == next(iter(cache.learned.values()))


def test_a_statement_leaves_no_page_to_the_cyclic_collector(connector):
    """With the collector off, a statement's device arrays are all gone
    when it ends: nothing that holds a page sits in a reference cycle
    (the sampling profiler's frames, the executor's recursive closures).
    A statement that compiles nothing allocates little, so the collector
    comes by seldom, and on the chip `peak_hbm_gb` rose with every page
    that waited for it (PERF.md, PR 32)."""
    import jax

    from presto_tpu.server.cluster import TpuCluster

    sql = ("select o_orderpriority, count(*), sum(l_quantity) "
           "from orders, lineitem where l_orderkey = o_orderkey "
           "and l_shipdate > date '1995-03-15' "
           "group by o_orderpriority order by o_orderpriority")
    cluster = TpuCluster(connector, n_workers=2)
    try:
        want = cluster.execute_sql(sql)
        cluster.execute_sql(sql)         # capacities settled, tables cached
        gc.collect()
        gc.disable()
        try:
            live = len(jax.live_arrays())
            assert cluster.execute_sql(sql) == want
            deadline = time.time() + 30  # the tasks' threads wind down
            while len(jax.live_arrays()) > live and time.time() < deadline:
                time.sleep(0.05)
            assert len(jax.live_arrays()) <= live
        finally:
            gc.enable()
    finally:
        cluster.stop()


# ---- a fuse's dictionary is one object a content -------------------------

def _pages_of(dictionary: StringDict, codes_by_page):
    pages = []
    for codes in codes_by_page:
        col = Column.from_numpy(np.asarray(codes, np.int32), VARCHAR,
                                dictionary=dictionary)
        pages.append(Page.from_columns([col], len(codes), ("w",)))
    return pages


def test_a_fuse_makes_one_dictionary_for_the_same_words():
    words = [f"Customer#{i:09d}" for i in range(200)]
    # as the exchange decodes it: one sparse dictionary on every page
    wire = StringDict(words, sparse=True)
    fused = concat_pages_host(_pages_of(wire, [[3, 5, 7], [5, 11]]))
    again = concat_pages_host(_pages_of(wire, [[11, 3], [7, 5, 5]]))
    d = fused.columns[0].dictionary
    assert d.words == tuple(words[i] for i in (3, 5, 7, 11))
    assert again.columns[0].dictionary is d and not d.sparse
    # decoded anew (another statement's pull): the same words, the same
    # object; other words, another
    later = concat_pages_host(_pages_of(
        StringDict(words, sparse=True), [[7, 11], [3, 5]]))
    assert later.columns[0].dictionary is d
    other = concat_pages_host(_pages_of(wire, [[3, 5, 7], [5, 12]]))
    assert other.columns[0].dictionary is not d
    assert other.columns[0].dictionary.words[-1] == words[12]
    # two upstreams, a dictionary each: the union is interned too
    a = StringDict(words[:100], sparse=True)
    b = StringDict(words[100:], sparse=True)
    mixed = [concat_pages_host(_pages_of(a, [[1, 2]])
                               + _pages_of(b, [[0, 4]]))
             for _ in range(2)]
    assert mixed[0].columns[0].dictionary is mixed[1].columns[0].dictionary
    assert mixed[0].to_pylist() == [
        (words[1],), (words[2],), (words[100],), (words[104],)]
    # weak: nothing keeps a dictionary that no page or program names
    gone = weakref.ref(other.columns[0].dictionary)
    del other
    gc.collect()
    assert gone() is None
