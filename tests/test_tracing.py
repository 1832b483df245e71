"""The one span API (`presto_tpu/utils/tracing.py`): nesting on a thread
and across the propagated context, the monotonic span clock, the second
sink (a span under the JAX profiler comes back from the `.xplane.pb` as
`presto:<name>` with its attributes), the names the executor gives its
device programs, and the true placement of the worker's `op:` spans.
Pure and quick: no cluster here (the served path's spans are held in
tests/test_metrics.py, on its cluster; TPC-H Q18's `join_types` and
`agg_steps` through two workers in tests/test_q18_served.py, on its)."""

import glob
import itertools
import os
import re
import threading
import time
import types

import pytest

from presto_tpu.utils import tracing
from presto_tpu.utils.tracing import (
    TRACER, TraceContext, Tracer, current_trace, now, root_scope,
    trace_scope,
)


def _by_name(tracer, trace_id):
    return {s.name: s for s in tracer.get(trace_id)}


# ------------------------------------------------------------------ nesting

def test_a_span_parents_the_next_one_opened_on_its_thread():
    t = Tracer()
    with t.span("q1", "statement") as a:
        with t.span("q1", "plan") as b:
            with t.span("q1", "schedule") as c:
                pass
        with t.span("q1", "query") as d:
            pass
    assert a.parent_id == ""
    assert b.parent_id == a.span_id and c.parent_id == b.span_id
    assert d.parent_id == a.span_id      # b closed: a is current again
    assert all(s.end >= s.start for s in (a, b, c, d))
    # a sibling trace on the same thread does not adopt the open span
    with t.span("q1", "statement") as a:
        with t.span("q2", "statement") as other:
            pass
    assert other.parent_id == ""


def test_the_propagated_context_parents_a_threads_first_span():
    t = Tracer()
    with trace_scope("q7", "rootspan"):
        with t.span(None, "task_run") as run:      # id from the thread
            with t.span(None, "upload", bytes=10) as up:
                up.attributes["bytes"] = 12
    assert run.parent_id == "rootspan" and up.parent_id == run.span_id
    assert _by_name(t, "q7")["upload"].attributes["bytes"] == 12
    assert current_trace() is None


def test_a_helper_thread_records_under_the_span_it_works_for():
    t = Tracer()
    seen = []

    def fetch(here):
        with t.span(here.trace_id, "deserialize",
                    parent_id=here.parent_span_id, bytes=5) as s:
            seen.append(s)
        assert current_trace() is None       # no scope was installed

    with trace_scope("q8", "root"):
        with t.span(None, "exchange_pull") as pull:
            here = t.here()
            th = threading.Thread(target=fetch, args=(here,))
            th.start()
            th.join(10)
    assert not th.is_alive()
    assert here == TraceContext("q8", pull.span_id)
    assert seen[0].parent_id == pull.span_id
    assert "deserialize" in _by_name(t, "q8")


def test_a_thread_without_a_trace_annotates_and_stores_nothing():
    t = Tracer()
    with t.span(None, "upload", bytes=1) as s:
        s.attributes["bytes"] = 2
    assert s.end >= s.start and t.spans == {}
    assert t.record(None, "admission_wait", now() - 1.0, now()) is None
    assert t.spans == {}


def test_root_scope_is_decided_once_a_statement():
    with root_scope("outer", True) as ctx:
        assert ctx.trace_id == "outer"
        with root_scope("inner", False) as again:   # the cluster under
            assert again.trace_id == "outer"        # the statement server
    assert current_trace() is None
    with root_scope("outer", False) as ctx:
        assert ctx is None
        with root_scope("inner", True) as again:    # its draw is not asked
            assert again is None
        assert current_trace() is None
    with root_scope("later", True) as ctx:
        assert ctx.trace_id == "later"


def test_a_count_is_added_to_the_span_it_is_reported_under():
    t = Tracer()
    with trace_scope("q5"):
        with t.span(None, "upload", table="orders") as up:
            t.add("upload", resident=5)
            t.add("upload", resident=7)
            t.add("download", resident=100)     # no such span is open
            with t.span(None, "deserialize") as inner:
                t.add("upload", resident=1)     # not the innermost
        t.add("upload", resident=9)             # none open
    assert up.attributes == {"table": "orders", "resident": 12}
    assert inner.attributes == {}


def test_record_backdates_a_wait_and_marks_it():
    t = Tracer()
    t0 = now() - 0.25
    s = t.record("q9", "admission_wait", t0, now(), mark=True,
                 group="global")
    assert s.start == t0 and 240 <= s.attributes["waited_ms"] <= 2000
    assert s.attributes["group"] == "global"


# -------------------------------------------------------------------- clock

def test_a_span_survives_the_wall_clock_jumping_backwards(monkeypatch):
    t = Tracer()
    real = time.time
    with t.span("q1", "plan") as s:
        monkeypatch.setattr(time, "time", lambda: real() - 3600.0)
        time.sleep(0.01)
    monkeypatch.undo()
    assert 0.005 < s.duration_s < 5.0
    # epoch seconds on the wire, so dumps of several processes align
    assert abs(s.start - real()) < 60.0
    a = now()
    assert now() >= a


# ----------------------------------------------------------- the second sink

def test_a_span_under_the_profiler_comes_back_from_the_xplane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    t = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace_scope("q1", ""):
            with t.span(None, "upload", table="lineitem", bytes=10) as s:
                time.sleep(0.002)
                s.attributes["bytes"] = 4096          # known at the end
            t.record(None, "admission_wait", now() - 0.5, now(),
                     mark=True, group="global")
        with t.span(None, "deserialize", bytes=7):    # no trace: still
            pass                                      # annotated
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[-1]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.ANNOTATION_PREFIX):
                    found[ev.name] = (ev.duration_ns, dict(ev.stats))
    assert set(found) == {"presto:upload", "presto:admission_wait",
                          "presto:deserialize"}
    dur, stats = found["presto:upload"]
    assert dur >= 2e6 and stats == {"table": "lineitem", "bytes": 4096}
    assert 400 <= found["presto:admission_wait"][1]["waited_ms"] <= 5000
    assert found["presto:deserialize"][1] == {"bytes": 7}
    assert {s.name for s in t.get("q1")} == {"upload", "admission_wait"}


# ------------------------------------------------------------ program names

JOIN_SQL = ("select o_orderpriority, count(*) from orders join lineitem "
            "on l_orderkey = o_orderkey group by o_orderpriority")


@pytest.fixture(scope="module")
def engine():
    from presto_tpu.connectors import TpchConnector
    from presto_tpu.exec.engine import LocalEngine
    return LocalEngine(TpchConnector(0.01))


def test_a_program_is_named_by_its_root_and_its_plan(engine):
    from presto_tpu.exec.executor import Executor
    from presto_tpu.exec.split_executor import SplitExecutor
    plan = engine.plan_sql(JOIN_SQL)
    other = engine.plan_sql(JOIN_SQL.replace("count(*)", "max(l_tax)"))
    a = Executor(engine.connector)
    b = SplitExecutor(engine.connector)       # built anew for every task
    name = a.program_name(plan)
    assert re.fullmatch(r"presto_[A-Za-z]+_[0-9a-f]{8}", name)
    assert b.program_name(plan) == name       # nothing of the run in it
    assert a.program_name(other) != name
    # an island's program is named by the island's own root
    join = next(c for c in _walk(plan) if type(c).__name__ == "JoinNode")
    assert a.program_name(join).startswith("presto_Join_")


def _walk(node):
    yield node
    for c in node.children():
        if c is not None:
            yield from _walk(c)


def test_the_device_sees_the_name_and_the_operators(engine):
    """The function handed to jax.jit carries the name (the device
    trace's module is `jit_<name>`), its `dispatch` span says which
    operators it holds, and each operator is traced under its
    named_scope."""
    from presto_tpu.config import Session
    from presto_tpu.exec.executor import Executor
    ex = Executor(engine.connector,
                  session=Session({"collect_stats": "true"}))
    plan = engine.plan_sql(JOIN_SQL)
    t_before = now()
    with trace_scope("prog1", ""):
        ex.execute(plan)
    t_after = now()
    dispatched = [s for s in TRACER.get("prog1") if s.name == "dispatch"]
    programs = {s.attributes["program"]: s.attributes for s in dispatched}
    compiled = {"jit_" + e.fn.__name__
                for e in ex.programs.jitted.values()}
    assert compiled == set(programs) and len(programs) >= 2
    joins = [a for a in programs.values()
             if "Join" in a["operators"].split("+")]
    assert joins and all(a["first_call"] for a in programs.values())
    assert any(p.startswith("jit_presto_Join_") for p in programs)
    # the profiled branch times every island where it ran
    profile = ex.last_island_profile
    assert len(profile) >= 2
    starts = [e["t0"] for e in profile]
    assert starts == sorted(starts)
    assert t_before <= starts[0] and \
        starts[-1] + profile[-1]["seconds"] <= t_after
    for e, nxt in zip(profile, profile[1:]):
        assert e["t0"] + e["seconds"] <= nxt["t0"] + 1e-6
    waits = [s for s in TRACER.get("prog1") if s.name == "device_wait"]
    assert waits and {s.attributes["sync"] for s in waits} == {"per_island"}
    uploads = [s for s in TRACER.get("prog1") if s.name == "upload"]
    assert {s.attributes["table"] for s in uploads} == {"orders", "lineitem"}
    assert all(s.attributes["bytes"] + s.attributes.get("resident", 0) > 0
               for s in uploads)


@pytest.mark.parametrize("sql, paths, types", [
    # FK join, unique build keys: merge_join answers
    (JOIN_SQL, ["merge"], "INNER"),
    # duplicate keys on both sides: the dup counter re-lowers the program
    # onto the expansion join, and the second dispatch says so
    ("select count(*) from orders join lineitem on l_suppkey = o_custkey",
     ["merge", "expansion"], "INNER"),
    # semi joins never fall back; a cross join never merges
    ("select count(*) from orders where o_custkey in "
     "(select l_suppkey from lineitem)", ["merge"], "SEMI"),
    ("select count(*) from nation, region", ["expansion"], "INNER"),
], ids=["unique_build", "duplicate_build", "semi", "cross"])
def test_dispatch_says_how_it_joins(engine, request, sql, paths, types):
    """`join_paths` on the `dispatch` span: what can silently bypass
    merge_join is the re-lowering onto hash_join, and the span names it.
    Beside it `join_types`, a JoinNode each, and `agg_steps`, an
    AggregationNode each; neither on a program that holds none."""
    from presto_tpu.exec.executor import Executor
    ex = Executor(engine.connector)
    trace_id = "join_paths_" + request.node.callspec.id
    with trace_scope(trace_id, ""):
        ex.execute(engine.plan_sql(sql))
    dispatched = [s.attributes for s in TRACER.get(trace_id)
                  if s.name == "dispatch"]
    # (a run of one path: the expansion join may grow its capacity and
    # dispatch again)
    seen = [a["join_paths"] for a in dispatched if "join_paths" in a]
    assert [path for path, _run in itertools.groupby(seen)] == paths
    assert {a["join_types"] for a in dispatched
            if "join_types" in a} == {types}
    for a in dispatched:
        ops = a["operators"].split("+")
        assert ("join_types" in a) == ("Join" in ops) == ("join_paths" in a)
        assert ("agg_steps" in a) == ("Aggregation" in ops)
        if "agg_steps" in a:
            # one executor runs the whole plan: no step but SINGLE
            assert set(a["agg_steps"].split("+")) == {"SINGLE"}


def test_upload_counts_only_what_moves(engine):
    """A whole-table scan answers from the table's device cache the
    second time (`HostTable.page`): the span says what the device held
    and counts no byte that did not move."""
    from presto_tpu.exec.executor import Executor
    ex = Executor(engine.connector)
    plan = engine.plan_sql("select sum(l_discount) from lineitem")

    def uploads(trace_id):
        with trace_scope(trace_id, ""):
            ex.execute(plan)
        return [s.attributes for s in TRACER.get(trace_id)
                if s.name == "upload"]

    (first,) = uploads("up1")
    (again,) = uploads("up2")
    moved = first["bytes"] + first.get("resident", 0)
    assert moved > 1000 and again["resident"] >= moved - 64
    assert 0 <= again["bytes"] < 64


def test_each_operator_is_traced_under_its_named_scope(engine):
    from presto_tpu.exec.executor import Executor
    ex = Executor(engine.connector)
    from presto_tpu.expr.params import lift_plan
    plan = engine.plan_sql(
        "select count(*) from lineitem where l_quantity < 10")
    ex.execute(plan)
    program, = ex.programs.jitted.values()
    fn, scans, about = program.fn, program.scans, program.about
    assert about["program"] == "jit_" + fn.__name__
    text = fn.lower([ex._fetch(s) for s in scans],
                    lift_plan(plan).values).as_text(debug_info=True)
    # (a scan or an output relabels pages and leaves no operation)
    assert re.search(r'loc\("[^"]*\bAggregation\b', text)


# ----------------------------------------------------------------- op: spans

def test_op_spans_start_where_their_island_started():
    from presto_tpu.server.task_manager import Task, TpuTaskManager
    task = Task("q5.2.0.1.0")
    task.trace_ctx = TraceContext("ops1", "rootspan")
    task.start_time = 1.0          # an invented placement started here
    ex = types.SimpleNamespace(
        last_memory_estimate=0, last_node_rows={}, _node_map={},
        last_island_profile=[
            {"root": "Join", "t0": 1000.5, "seconds": 0.25, "rows": 7},
            {"root": "Aggregation", "t0": 1002.0, "seconds": 0.5,
             "rows": 3}])
    manager = types.SimpleNamespace(node_id="tpu-worker-9")
    TpuTaskManager._collect_stats(manager, task, ex)
    ops = sorted((s for s in TRACER.get("ops1")
                  if s.name.startswith("op:")), key=lambda s: s.start)
    assert [(s.name, s.start, s.end) for s in ops] == [
        ("op:Join", 1000.5, 1000.75), ("op:Aggregation", 1002.0, 1002.5)]
    assert all(s.parent_id == "rootspan" and s.attributes["worker"]
               == "tpu-worker-9" for s in ops)
    assert ops[0].attributes["rows"] == 7
