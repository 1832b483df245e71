"""The four readers of the served path's fixed-cost spans (PR 37) on a
hand-made window (`fixtures/served_span_events.json`): the statement
protocol's markers, the task POSTs (a sum), the task notices (a union)
and the ordered root merge's blocked time (0.0 where it never waited,
nothing where no merge ran); each silent on the parent commit's spans.
Then the order of `per_layer`: `test_q01.py` and `test_q06.py` pin their
readers as its *last* entries and fail on that line once entries follow
(neither is this PR's to edit), so their pins are made here with this
PR's four after them. Pure, seconds, no cluster.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_served_span_readers.py -q
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

READERS = ("protocol_ms_per_stmt", "task_post_ms_per_stmt",
           "task_notice_ms_per_stmt", "root_merge_wait_ms_per_stmt")
NEW_SPANS = ("statement_post", "statement_results", "task_post",
             "task_notice", "root_merge")


@pytest.fixture(scope="module")
def fx():
    return qgen.load_json("fixtures", "served_span_events.json")


@pytest.fixture
def spans(fx):
    return [span_reduce.backdated(*e) for e in fx["events"]]


@pytest.fixture
def ctx(fx, spans, monkeypatch):
    monkeypatch.setattr(span_reduce, "load", lambda path: {
        "spans": spans, "host": {}, "modules": {"/device:TPU:0": []}})
    lo, hi = fx["window"]
    return {"records": [{}] * fx["statements"], "trace": {
        "path": "fixture", "lo_s": lo, "hi_s": hi,
        "busiest": "/device:TPU:0", "devices": {"/device:TPU:0": []},
        "compiling": [], "in_statement": []}}


def _read(name, ctx):
    return qgen.load_py("layer_metrics", name + ".py").read(ctx)


def test_the_protocol_and_notice_markers_are_backdated(spans):
    post = next(s for s in spans if s.name == "statement_post"
                and s.start_s > 100)
    assert (post.start_s, post.end_s) == pytest.approx((100.21, 100.25))
    notice = next(s for s in spans if s.name == "task_notice"
                  and s.thread == "W2")
    assert (notice.start_s, notice.end_s) == pytest.approx(
        (102.015, 102.03))
    merge = next(s for s in spans if s.name == "root_merge"
                 and s.start_s == 103.0)
    assert merge.end_s == pytest.approx(103.12)   # a span, not moved


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_the_window(name, fx, ctx):
    assert _read(name, ctx) == pytest.approx(fx["expected"][name])


def test_task_posts_are_summed_and_notices_united(fx, ctx):
    """Posts made at once each count (a concurrent-post change moves
    `coordinator_ms_per_stmt`, not this); notices that overlap count
    once, as wall the coordinator waited."""
    want = fx["expected"]
    assert span_reduce.ms_per_stmt(ctx, "task_post") == pytest.approx(
        want["task_post_union_ms_per_stmt"])
    assert _read("task_post_ms_per_stmt", ctx) > \
        span_reduce.ms_per_stmt(ctx, "task_post")
    assert _read("task_notice_ms_per_stmt", ctx) == pytest.approx(
        span_reduce.ms_per_stmt(ctx, "task_notice"))


def test_a_merge_that_never_waited_reads_zero(ctx, spans):
    for s in spans:
        if s.name == "root_merge":
            s.stats["blocked_ms"] = 0.0
    assert _read("root_merge_wait_ms_per_stmt", ctx) == 0.0


def test_no_merge_in_the_window_reads_nothing(ctx, spans):
    spans[:] = [s for s in spans if s.name != "root_merge"
                or s.end_s < 100.0]      # warm-up's alone
    assert _read("root_merge_wait_ms_per_stmt", ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_is_silent_on_the_parents_spans(name, ctx, spans):
    assert _read(name, {"records": [{}], "trace": None}) is None
    assert _read(name, dict(ctx, records=[])) is None
    spans[:] = [s for s in spans if s.name not in NEW_SPANS]
    assert _read(name, ctx) is None


def test_the_readers_are_the_benchmarks_last_entries():
    """Every assertion of the order pins of `test_q06.py` and
    `test_q01.py` (PR 26's thirteen, PR 29's two, PR 33's two and PR
    35's one, together and in order), with this PR's four after them,
    as ISSUE 37 gives them."""
    import test_q01
    import test_q06
    import test_q18
    import test_spans
    pinned = (list(test_spans.NEW_READERS) + list(test_q18.READERS)
              + list(test_q06.READERS) + [test_q01.READER])
    assert len(pinned) == 18
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert len(entries) == len(names)
    assert names[-22:-4] == pinned
    assert names[-4:] == list(READERS)
    assert {n for n in test_spans.NEW_READERS
            if "workloads" in entries[n]} == {
        "exchange_pull_ms_per_stmt", "join_device_ms_per_stmt",
        "aggregate_device_ms_per_stmt"}
    for name in test_q18.READERS:
        assert entries[name]["workloads"] == ["q18_serial"]
    for name in test_q06.READERS:
        assert entries[name]["workloads"] == ["q06_serial"]
    assert entries[test_q01.READER]["workloads"] == ["q01_serial"]
    assert entries["compile_s_per_stmt"]["workloads"] == [
        "q03_serial", "q18_serial"]
    layers = {"protocol_ms_per_stmt": "client / statement protocol",
              "task_post_ms_per_stmt": "coordinator",
              "task_notice_ms_per_stmt": "coordinator",
              "root_merge_wait_ms_per_stmt":
                  "serde / root merge / delivery"}
    for name in READERS:
        want = {"name": name, "unit": "ms", "better": "lower",
                "source": "program_span", "layer": layers[name],
                "moves": "wall_p50_s"}
        if name == "root_merge_wait_ms_per_stmt":
            want["workloads"] = ["q01_serial"]
        assert entries[name] == want
    # each layer is one that BENCHMARK.json already named
    older = {m["layer"] for m in bench["per_layer"][:-4]}
    assert set(layers.values()) <= older
