"""span_reduce.py and the readers of the program's spans, on a hand-made
window (`fixtures/span_events.json`): pure, seconds, no cluster.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_spans.py -q
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import qgen  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

#: the readers this file's fixture feeds: the per-layer metrics that read
#: the program's spans or the modules line
NEW_READERS = (
    "admission_wait_ms_per_stmt", "coordinator_ms_per_stmt",
    "telemetry_ms_per_stmt", "lower_ms_per_stmt", "upload_ms_per_stmt",
    "upload_mb_per_stmt", "exchange_pull_ms_per_stmt", "serde_ms_per_stmt",
    "root_collect_ms_per_stmt", "idle_unattributed_pct",
    "eager_device_programs_per_stmt", "join_device_ms_per_stmt",
    "aggregate_device_ms_per_stmt")


@pytest.fixture(scope="module")
def fx():
    return qgen.load_json("fixtures", "span_events.json")


@pytest.fixture(scope="module")
def spans(fx):
    return [span_reduce.backdated(*e) for e in fx["events"]]


@pytest.fixture(scope="module")
def idle(fx):
    lo, hi = fx["window"]
    busy = trace_reduce.busy_intervals(
        [tuple(e) for e in fx["device_events"]], lo, hi)
    return trace_reduce.gaps(busy, lo, hi)


def test_a_marker_is_backdated_by_what_it_waited(spans):
    wait = next(s for s in spans if s.name == "admission_wait")
    assert (wait.start_s, wait.end_s) == pytest.approx((104.5, 104.6))
    pull = next(s for s in spans if s.name == "exchange_pull")
    assert (pull.start_s, pull.end_s) == pytest.approx((104.9, 105.5))
    plan = next(s for s in spans if s.name == "plan")
    assert (plan.start_s, plan.end_s) == pytest.approx((100.2, 100.8))


def test_interval_arithmetic():
    assert span_reduce.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [
        (0, 2), (3, 5), (7, 9)]
    assert span_reduce.subtract([(0, 1), (4, 6)], []) == [(0, 1), (4, 6)]
    assert span_reduce.subtract([(0, 1)], [(0, 1)]) == []
    assert span_reduce.intersect([(0, 4), (6, 9)], [(3, 7), (8, 20)]) == [
        (3, 4), (6, 7), (8, 9)]


def test_wall_is_the_union_over_threads_clipped_to_the_window(fx, spans):
    lo, hi = fx["window"]
    # upload: T2 100.6-100.9 and T3 100.7-101.0 overlap (0.4), and the
    # one from 99.4 is cut at the window's start (0.2); serialize is cut
    # at its end; the marker covers the 0.1 s before it
    for name, want in fx["expected"]["wall_s"].items():
        assert span_reduce.wall_s(spans, (name,), lo, hi) == \
            pytest.approx(want), name
    assert span_reduce.wall_s(spans, ("upload", "dispatch"), lo, hi) == \
        pytest.approx(0.2 + 1.2 + 0.4)   # 100.6-101.8 is one stretch


def test_dispatch_less_compile(fx, spans):
    lo, hi = fx["window"]
    # 100.9-101.8 less the compile 101.0-101.5, plus 105.5-105.9
    left = span_reduce.lowering(spans, fx["compile_intervals"], lo, hi)
    assert trace_reduce.total(left) == pytest.approx(
        fx["expected"]["dispatch_less_compile_s"])
    assert left == [pytest.approx(i) for i in
                    [(100.9, 101.0), (101.5, 101.8), (105.5, 105.9)]]


def test_counts_clip_to_the_window_with_their_span(fx, spans):
    lo, hi = fx["window"]
    # 1000 + 500 + 1200 * 0.2 / 0.8; 10 * 0.5 / 1.0
    assert span_reduce.attribute_sum(spans, "upload", "bytes", lo, hi) == \
        pytest.approx(fx["expected"]["upload_bytes"])
    assert span_reduce.attribute_sum(spans, "serialize", "bytes", lo, hi) \
        == pytest.approx(fx["expected"]["serialize_bytes"])
    assert span_reduce.attribute_sum(spans, "plan", "bytes", lo, hi) == 0.0


def test_idle_is_handed_out_in_order_and_sums_to_the_idle_time(
        fx, spans, idle):
    assert trace_reduce.total(idle) == pytest.approx(fx["expected"]["idle_s"])
    got = span_reduce.attribute_idle(
        idle, fx["compile_intervals"], spans, fx["statement_intervals"])
    assert list(got) == ["compiling", *span_reduce.LEAVES,
                         "unattributed_in_statement", "between_statements"]
    assert got == pytest.approx(fx["expected"]["idle_by_owner"])
    assert sum(got.values()) == pytest.approx(trace_reduce.total(idle))
    # exclusive, work before a long poll: the decode on a fetcher's
    # thread owns its 0.2 s of the pull it overlaps, the pull what is left
    assert got["deserialize"] == pytest.approx(0.2)
    assert got["exchange_pull"] == pytest.approx(0.4)
    assert span_reduce.LEAVES[-1] == "exchange_pull"
    # the compile inside `dispatch` is the compiler's, the rest dispatch's
    assert got["compiling"] == pytest.approx(0.5)
    assert span_reduce.attribute_idle([], [], spans, []) == {}


def test_a_container_owns_no_idle_time(fx, spans, idle):
    """101.8-102.0 lies under task_run and device_wait only, 107-108.5
    under statement, query and await_tasks only: unattributed."""
    gap_c = idle[-1]
    cover = span_reduce.gap_cover(
        gap_c, fx["compile_intervals"], spans, fx["statement_intervals"])
    want = fx["expected"]["gap_c"]
    assert cover["seconds"] == pytest.approx(want["seconds"])
    assert cover["owners"] == pytest.approx(want["owners"])
    assert cover["containers"] == pytest.approx(want["containers"])
    only = span_reduce.attribute_idle(
        [(101.8, 102.0)], fx["compile_intervals"], spans,
        fx["statement_intervals"])
    assert only["unattributed_in_statement"] == pytest.approx(0.2)
    # 104.8-104.9: a consumer waits for its producers (`exchange_wait`)
    # and no GET has landed anything yet
    waiting = span_reduce.attribute_idle(
        [(104.8, 104.9)], fx["compile_intervals"], spans,
        fx["statement_intervals"])
    assert waiting["unattributed_in_statement"] == pytest.approx(0.1)
    assert waiting["exchange_pull"] == 0.0
    assert "exchange_wait" in span_reduce.CONTAINERS
    assert set(span_reduce.CONTAINERS).isdisjoint(span_reduce.LEAVES)


def test_the_per_island_sync_shows_between_a_tasks_islands(fx, spans, idle):
    """T2's task waits for the device twice (101.8-103.0, 103.45-103.5):
    between the two it is between islands, and the device is idle."""
    between = span_reduce.between_islands(spans)
    assert between == [pytest.approx(i)
                       for i in fx["expected"]["between_islands"]]
    assert trace_reduce.total(span_reduce.intersect(idle, between)) == \
        pytest.approx(0.45)
    chain = [s._replace(stats={"sync": "chain"}) if s.name == "device_wait"
             else s for s in spans]
    assert span_reduce.between_islands(chain) == []


def test_device_seconds_by_program(fx, spans):
    lo, hi = fx["window"]
    events = [tuple(e) for e in fx["module_events"]]
    got = span_reduce.module_seconds(events, lo, hi)
    assert {k: list(v) for k, v in got.items()} == {
        k: pytest.approx(v)
        for k, v in fx["expected"]["module_seconds"].items()}
    assert span_reduce.program_of("jit_presto_Join_1a2b3c4d(99)") == \
        "jit_presto_Join_1a2b3c4d"
    assert span_reduce.program_of("jit_add") == "jit_add"
    assert span_reduce.eager_executions(events, lo, hi) == \
        fx["expected"]["eager_executions"]
    assert span_reduce.modules_with(spans, "Join") == {
        "jit_presto_Join_aaaaaaaa"}
    assert span_reduce.modules_with(spans, "Aggregation",
                                    without="Join") == {
        "jit_presto_Aggregation_bbbbbbbb"}
    assert span_reduce.modules_with(spans, "Window") == set()
    # an operation is its module's: fusion.2 starts inside the second
    # Join execution, fusion.3 and fusion.4 inside the Aggregation's
    ops = [tuple(e) for e in fx["device_events"]]
    assert span_reduce.ops_by_program(ops, events, lo, hi, 3) == [
        ("jit_presto_Join_aaaaaaaa", "fusion.2", pytest.approx(1.0)),
        ("jit_presto_Aggregation_bbbbbbbb", "fusion.3",
         pytest.approx(0.75)),
        ("jit_presto_Join_aaaaaaaa", "fusion.1", pytest.approx(0.5))]
    assert span_reduce.ops_by_program(ops, [], lo, hi, 1) == [
        ("?", "fusion.2", pytest.approx(1.0))]


@pytest.fixture
def ctx(fx, spans, monkeypatch):
    lo, hi = fx["window"]
    monkeypatch.setattr(span_reduce, "load", lambda path: {
        "spans": spans, "host": {},
        "modules": {"/device:TPU:0": [tuple(e)
                                      for e in fx["module_events"]]}})
    return {"records": [{}, {}], "trace": {
        "path": "fixture", "lo_s": lo, "hi_s": hi, "busiest":
        "/device:TPU:0", "devices": {"/device:TPU:0": [
            tuple(e) for e in fx["device_events"]]},
        "compiling": fx["compile_intervals"],
        "in_statement": fx["statement_intervals"]}}


def _read(name, ctx):
    return qgen.load_py("layer_metrics", name + ".py").read(ctx)


def test_the_readers_over_the_statements_attempted(fx, ctx):
    want = fx["expected"]
    assert _read("admission_wait_ms_per_stmt", ctx) == pytest.approx(50.0)
    assert _read("coordinator_ms_per_stmt", ctx) == pytest.approx(300.0)
    assert _read("telemetry_ms_per_stmt", ctx) is None    # no such span
    # no task_plan here: dispatch less compile, 0.8 s over 2
    assert _read("lower_ms_per_stmt", ctx) == pytest.approx(400.0)
    assert _read("upload_ms_per_stmt", ctx) == pytest.approx(300.0)
    assert _read("upload_mb_per_stmt", ctx) == pytest.approx(0.0009)
    assert _read("exchange_pull_ms_per_stmt", ctx) == pytest.approx(300.0)
    # download 0.4 + serialize 0.5 + deserialize 0.2
    assert _read("serde_ms_per_stmt", ctx) == pytest.approx(550.0)
    assert _read("root_collect_ms_per_stmt", ctx) == pytest.approx(500.0)
    assert _read("idle_unattributed_pct", ctx) == pytest.approx(
        want["idle_unattributed_pct"])
    assert _read("eager_device_programs_per_stmt", ctx) == \
        pytest.approx(1.0)
    assert _read("join_device_ms_per_stmt", ctx) == pytest.approx(
        want["join_device_ms_per_stmt"])
    assert _read("aggregate_device_ms_per_stmt", ctx) == pytest.approx(
        want["aggregate_device_ms_per_stmt"])


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_without_a_trace(name, ctx):
    assert _read(name, {"records": [{}], "trace": None}) is None
    assert _read(name, dict(ctx, records=[])) is None
    # a program without spans (the parent commit): the span readers are
    # silent, the two that read the device alone still answer
    span_reduce.load("fixture")["spans"].clear()
    got = _read(name, ctx)
    if name == "idle_unattributed_pct":
        assert got == pytest.approx(100 * 5.5 / 6.0)
    elif name == "eager_device_programs_per_stmt":
        assert got == pytest.approx(1.0)
    else:
        assert got is None


def test_the_readers_are_the_benchmarks_entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW_READERS) <= set(entries)
    assert [m["name"] for m in bench["per_layer"]][-13:] == list(NEW_READERS)
    sources = {entries[n]["source"] for n in NEW_READERS}
    assert sources == {"program_span", "program_counter", "device_trace"}
    listed = {n for n in NEW_READERS if "workloads" in entries[n]}
    assert listed == {"exchange_pull_ms_per_stmt", "join_device_ms_per_stmt",
                      "aggregate_device_ms_per_stmt"}


def test_main_prints_a_real_traces_spans(tmp_path, capsys):
    """A real (CPU) profiler trace has no device plane: `load` finds the
    program's spans and the client's marks, and the `__main__` says what
    it misses instead of printing numbers."""
    import jax
    from presto_tpu.utils.tracing import TRACER, now
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.SYNC_MARK):
        pass
    with jax.profiler.TraceAnnotation("bench_post"):
        with TRACER.span(None, "upload", table="t", bytes=3):
            pass
        TRACER.record(None, "admission_wait", now() - 0.25, now(),
                      mark=True)
    jax.profiler.stop_trace()
    span_reduce.load.cache_clear()
    data = span_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    by = {s.name: s for s in data["spans"]}
    assert set(by) == {"upload", "admission_wait"}
    assert by["upload"].stats == {"table": "t", "bytes": 3}
    assert by["admission_wait"].end_s - by["admission_wait"].start_s == \
        pytest.approx(0.25, abs=0.05)
    assert len(data["host"]["bench_post"]) == 1 and data["modules"] == {}
    with pytest.raises(SystemExit, match="no device plane"):
        span_reduce.main([str(tmp_path)])
    assert span_reduce.main([]) == 2
