"""The benchmark's pure code: names and files of BENCHMARK.json, qgen
draws, window arithmetic, the trace reduction on a hand-made event list,
the roofline's bytes table, the comparison. Seconds, no cluster, no jit.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_benchmark.py -q
"""

import json
import os
import random
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402
import qgen  # noqa: E402
import roofline  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_keys_are_the_contracts(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_cell_resolves_its_files_by_name(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        entry = configs[w["config"]]
        used.add(w["config"])
        assert entry["file"].startswith(tuple(
            p + "/" for p in bench["paths"]))
        with open(os.path.join(REPO, entry["file"])) as f:
            config = json.load(f)
        assert set(entry["reduced"]) == set(config["reduced"])
        traffic = qgen.load_traffic(w["traffic"])
        assert traffic["loop"] == "closed" and traffic["clients"] >= 1
        for t in traffic["cycle"]:
            query = qgen.load_query(t)
            assert callable(compare.load_reference(query))
            assert set(query["limits"]) == {"wrong_cells", "max_rel_err"}
            for table in query["reads"]:
                assert table in config["rows"]
    assert used == set(configs)  # a configuration keeps a cell
    for group, where in (("per_layer", "layer_metrics"),
                         ("end_to_end", "end_to_end")):
        for m in bench[group]:
            reader = qgen.load_py(where, m["name"] + ".py")
            assert callable(reader.read), m["name"]
            for cell in m.get("workloads", []):
                assert cell in {w["name"] for w in bench["workloads"]}


def test_nothing_under_paths_waits_for_a_cell(bench):
    """Every traffic, query, configuration and reader file is reached by a
    cell or a metric of BENCHMARK.json: what no run drives rots unseen."""
    traffics = {w["traffic"] for w in bench["workloads"]}
    templates = {t for name in traffics
                 for t in qgen.load_traffic(name)["cycle"]}
    readers = {g: {m["name"] for m in bench[g]}
               for g in ("per_layer", "end_to_end")}

    def stems(*parts):
        return {os.path.splitext(f)[0] for f in os.listdir(
            os.path.join(BENCH, *parts)) if not f.startswith("_")}
    assert stems("traffic") == traffics
    assert stems("queries") == templates
    assert stems("configs") == {c["name"] for c in bench["configs"]}
    assert stems("layer_metrics") == readers["per_layer"]
    assert stems("end_to_end") == readers["end_to_end"]


def test_every_cell_reports_a_metric_of_each_kind(bench):
    for w in bench["workloads"]:
        def has(group):
            return [m["name"] for m in bench[group]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in has("end_to_end") and len(has("end_to_end")) > 1
        assert has("per_layer")


# TPC-H's substitution rules as qgen spells them: Q6's (clause 2.4.6.3)
# and Q1's (2.4.1.3), the cells of which wait in PERF.md's Open questions
Q6_RULES = {"name": "q6", "sql": "{DATE} {DISCOUNT} {QUANTITY}", "params": {
    "DATE": {"rule": "date_jan1", "lo": 1993, "hi": 1997},
    "DISCOUNT": {"rule": "decimal", "lo": 0.02, "hi": 0.09, "step": 0.01},
    "QUANTITY": {"rule": "int", "lo": 24, "hi": 25}}}
Q1_RULES = {"name": "q1", "sql": "{DELTA}", "params": {
    "DELTA": {"rule": "int", "lo": 60, "hi": 120}}}
Q3_RULES = {"name": "q3", "sql": "{SEGMENT} {DATE}", "params": {
    "SEGMENT": {"rule": "choice", "values": ["AUTOMOBILE", "BUILDING"]},
    "DATE": {"rule": "date", "lo": "1995-03-01", "hi": "1995-03-31"}}}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_qgen_draws_within_the_clauses_ranges_and_repeat(seed):
    seen = set()
    for k in range(200):
        p, sql = qgen.statement(Q6_RULES, random.Random(f"{seed}:{k}"))
        assert p["DATE"] in {f"{y}-01-01" for y in range(1993, 1998)}
        assert p["DISCOUNT"] in {f"0.0{d}" for d in range(2, 10)}
        assert p["QUANTITY"] in (24, 25)
        assert "{" not in sql and p["DATE"] in sql
        seen.add(p["DISCOUNT"])
        p1, _ = qgen.statement(Q1_RULES, random.Random(f"{seed}:{k}"))
        assert 60 <= p1["DELTA"] <= 120
        p3, _ = qgen.statement(Q3_RULES, random.Random(f"{seed}:{k}"))
        assert "1995-03-01" <= p3["DATE"] <= "1995-03-31"
        assert p3["SEGMENT"] in ("AUTOMOBILE", "BUILDING")
        assert (p, sql) == qgen.statement(
            Q6_RULES, random.Random(f"{seed}:{k}"))
    assert len(seen) == 8  # the whole domain, none carved out
    with pytest.raises(ValueError):
        qgen.draw({"rule": "zipf"}, random.Random(0))


def test_q03_is_fixed_at_the_validation_parameters():
    q3 = qgen.load_query("q03")
    drawn = {json.dumps(qgen.statement(q3, random.Random(s))[0])
             for s in range(20)}
    assert drawn == {json.dumps({"DATE": "1995-03-15",
                                 "SEGMENT": "BUILDING"})}


@pytest.mark.parametrize("seed", [1, 2, 99, 2**31 + 5])
def test_a_seed_reorders_the_mix_and_does_not_change_it(seed):
    traffic = {"clients": 4, "cycle": ["q6", "q6", "q6", "q3"]}
    queries = {"q6": Q6_RULES, "q3": Q3_RULES}
    assert sorted(qgen.offsets(traffic, seed)) == [0, 1, 2, 3]
    first = []
    for c in range(traffic["clients"]):
        stream = qgen.client_stream(traffic, queries, seed, c)
        mine = [next(stream) for _ in range(8)]
        again = qgen.client_stream(traffic, queries, seed, c)
        assert mine == [next(again) for _ in range(8)]
        assert [m[0] for m in mine].count("q3") == 2
        first.append(mine[0][0])
    assert sorted(first) == ["q3"] + ["q6"] * 3


def test_percentile_interpolates_over_all_values():
    xs = [4.0, 1.0, 3.0, 2.0, 5.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 0) == 1.0 and stats.percentile(xs, 100) == 5.0
    assert stats.percentile(xs, 95) == pytest.approx(4.8)
    assert stats.percentile([1.0, 2.0], 50) == 1.5
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile(xs, 101)


def test_a_stalled_statement_moves_the_rate():
    def recs(last_done):
        return [{"ok": True, "t_post": 10.0, "t_done": 12.0},
                {"ok": True, "t_post": 12.0, "t_done": 14.0},
                {"ok": True, "t_post": 14.0, "t_done": last_done},
                {"ok": False, "t_post": 14.0, "t_done": 15.0}]
    brisk, stalled = recs(20.0), recs(50.0)
    assert stats.walls(stalled) == [2.0, 2.0, 36.0]  # the failure has none
    w0 = stats.window_length(10.0, [r["t_done"] for r in brisk], 10.0)
    w1 = stats.window_length(10.0, [r["t_done"] for r in stalled], 10.0)
    assert (w0, w1) == (10.0, 40.0)
    assert stats.per_hour(3, w0) == 1080.0 and stats.per_hour(3, w1) == 270.0
    assert stats.window_length(10.0, [], 10.0) == 10.0
    with pytest.raises(ValueError):
        stats.per_hour(1, 0.0)


@pytest.fixture(scope="module")
def fixture_trace():
    return qgen.load_json("fixtures", "trace_events.json")


def test_trace_reduction_busy_idle_and_top_ops(fixture_trace):
    fx = fixture_trace
    lo, hi = fx["window"]
    events = [tuple(e) for e in fx["device_events"]]
    busy = trace_reduce.busy_intervals(events, lo, hi)
    assert trace_reduce.total(busy) == pytest.approx(fx["expected"]["busy_s"])
    idle = trace_reduce.gaps(busy, lo, hi)
    assert [list(g) for g in idle] == fx["expected"]["idle_gaps"]
    assert (trace_reduce.total(idle) + trace_reduce.total(busy)
            == pytest.approx(hi - lo))
    assert trace_reduce.top_ops(events, lo, hi) == fx["expected"]["top_ops"]
    assert trace_reduce.busy_intervals([], lo, hi) == []
    assert trace_reduce.gaps([], lo, hi) == [(lo, hi)]


def test_idle_gaps_are_attributed_to_what_the_host_did(fixture_trace):
    fx = fixture_trace
    idle = [tuple(g) for g in fx["expected"]["idle_gaps"]]
    got = trace_reduce.attribute_gaps(idle, fx["compile_intervals"],
                                      fx["statement_intervals"])
    assert got == pytest.approx(fx["expected"]["idle_by_kind"])
    assert sum(got.values()) == pytest.approx(trace_reduce.total(idle))
    longest = trace_reduce.longest_gaps(idle, fx["compile_intervals"],
                                        fx["statement_intervals"], 2)
    assert longest == [["compiling", 3.25],
                       ["between_statements", 2.5]]


def test_roofline_bytes_table_matches_the_connectors_dtypes():
    from presto_tpu.connectors import TpchConnector
    conn = TpchConnector(0.001)
    widths = qgen.load_json("column_bytes.json")
    for table in ("lineitem", "orders", "customer"):
        arrays = conn.table(table).arrays
        assert {c: a.dtype.itemsize for c, a in arrays.items()} \
            == widths[table]
    rows = {"lineitem": 6001917, "orders": 1500000, "customer": 150000}
    assert roofline.statement_bytes(
        {"reads": {"lineitem": ["l_quantity", "l_extendedprice",
                                "l_discount", "l_shipdate"]}}, rows) \
        == 6001917 * 28
    assert roofline.statement_bytes(qgen.load_query("q03"), rows) \
        == 6001917 * 28 + 1500000 * 24 + 150000 * 12


def test_peaks_hold_the_v5e_and_refuse_an_unknown_chip():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert roofline.hbm_floor_s(819e9, "TPU v5 lite", 4) == 0.25
    for kind in ("TPU v9", "cpu", "source"):
        with pytest.raises(KeyError):
            roofline.peaks(kind)


@pytest.mark.parametrize("got,wrong,err", [
    ([["A", 10.0, 3]], 0, 0.0),
    ([["A", 10.000001, 3]], 0, 1e-7),
    ([["A", 10.0, 4]], 1, 0.0),            # a count altered
    ([["B", 10.0, 3]], 1, 0.0),            # a key altered
    ([], 1, 0.0),                          # a row missing
    ([["A", 10.0, 3], ["A", 1.0, 1]], 1, 0.0),  # a row too many
    ([["A", float("nan"), 3]], 1, 0.0),
])
def test_row_gaps_counts_cells_and_measures_floats(got, wrong, err):
    gaps = compare.row_gaps(got, [["A", 10.0, 3]])
    assert gaps["wrong_cells"] == wrong
    assert gaps["max_rel_err"] == pytest.approx(err, rel=1e-3)


def test_judge_holds_each_template_to_its_own_limits():
    limits = {"a": {"wrong_cells": 0, "max_rel_err": 1e-9},
              "b": {"wrong_cells": 0, "max_rel_err": 1e-3}}
    recs = [{"template": "a", "rows": [[1.0]]},
            {"template": "b", "rows": [[1.0001]]},
            {"template": "a", "rows": [[1.0 + 1e-6]]}]
    v = compare.judge(recs, [[[1.0]]] * 3, limits)
    assert not v["correct"] and v["wrong_statements"] == [2]
    assert v["compared"]["a.max_rel_err"]["value"] == pytest.approx(1e-6)
    assert v["compared"]["b.max_rel_err"]["limit"] == 1e-3
    assert compare.judge(recs[:2], [[[1.0]]] * 2, limits)["correct"]
    assert not compare.judge([], [], limits)["correct"]  # nothing proven


def test_last_line_has_the_contracts_keys(bench):
    """The shape run.py prints, on a line kept from a CPU rehearsal."""
    line = qgen.load_json("fixtures", "result_line.json")
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert set(line["metrics"]) <= e2e and "setup_s" in line["metrics"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for slot in line["compared"].values():
        assert set(slot) == {"value", "limit"}


@pytest.fixture()
def fake_server():
    """A statement server of twenty lines: POST answers with a nextUri,
    two polls later the rows come; SQL 'boom' answers with an error."""
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def _send(self, obj):
            body = json.dumps(obj).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            sql = self.rfile.read(int(self.headers["Content-Length"]))
            base = f"http://127.0.0.1:{self.server.server_port}"
            if sql == b"boom":
                return self._send({"error": {"message": "no such table"}})
            self._send({"id": "q", "nextUri": f"{base}/v1/s/q/0"})

        def do_GET(self):
            n = int(self.path.rsplit("/", 1)[1])
            base = f"http://127.0.0.1:{self.server.server_port}"
            if n < 2:
                return self._send({"id": "q", "data": [[n]],
                                   "nextUri": f"{base}/v1/s/q/{n + 1}"})
            self._send({"id": "q", "data": [[n]]})

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()
    srv.server_close()
    t.join(5)
    assert not t.is_alive()


def test_client_follows_next_uri_and_counts_round_trips(fake_server):
    import time

    import client
    far = time.perf_counter() + 30
    rows, trips = client.run_statement(fake_server, "select 1", far)
    assert rows == [[0], [1], [2]] and trips == 4
    rec = client.timed_statement(fake_server, "t", {"P": 1}, "boom", far)
    assert not rec["ok"] and "no such table" in rec["error"]
    assert rec["t_done"] >= rec["t_post"]
    late = client.timed_statement(fake_server, "t", {}, "select 1",
                                  time.perf_counter() - 1)
    assert not late["ok"] and "TimeoutError" in late["error"]


def test_closed_loop_stops_issuing_at_the_close(fake_server):
    import itertools

    import client

    def stream(i):
        return (("t", {"k": k}, f"select {i}") for k in itertools.count())
    t_open, recs = client.closed_loop(fake_server, [stream(0), stream(1)],
                                      seconds=0.3)
    assert {r["client"] for r in recs} == {0, 1} and all(
        r["ok"] and r["trips"] == 4 for r in recs)
    assert all(r["t_post"] - t_open < 0.3 for r in recs)
    per_client = [r["params"]["k"] for r in recs if r["client"] == 0]
    assert per_client == list(range(len(per_client)))  # in order, no gap
    _t, few = client.closed_loop(fake_server, [stream(0)], seconds=0.0,
                                 min_statements=2)
    assert len(few) == 2


def test_end_to_end_readers_do_the_windows_arithmetic():
    recs = [{"ok": True, "t_post": 0.0, "t_done": 2.0},
            {"ok": True, "t_post": 2.0, "t_done": 6.0},
            {"ok": False, "t_post": 6.0, "t_done": 7.0},
            {"ok": True, "t_post": 7.0, "t_done": 10.0}]
    ctx = {"records": recs, "statements_right": 2, "window_s": 10.0,
           "setup_s": 81.5}

    def read(name, ctx):
        return qgen.load_py("end_to_end", name + ".py").read(ctx)
    assert read("qph", ctx) == 720.0  # a wrong statement is not counted
    assert read("wall_p50_s", ctx) == 3.0  # of all that came back
    assert read("setup_s", ctx) == 81.5
    assert read("wall_p50_s", dict(ctx, records=recs[2:3])) is None


def test_the_connector_is_found_by_the_name_the_program_gives_it():
    import run as bench_run
    for name in ("tpch", "tpcds"):
        conn = bench_run.make_connector(name, 0.001)
        assert conn.NAME == name and conn.scale_factor == 0.001
    with pytest.raises(SystemExit):
        bench_run.make_connector("oracle", 1.0)


def test_a_reader_can_pull_its_own_spans_from_the_trace(tmp_path):
    """A real (CPU) profiler trace: the clock mark is found, and a span
    opened by name comes back by its prefix, on the profiler's clock."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.SYNC_MARK):
        pass
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench_post"):
            jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    assert path and trace_reduce.load_xplane(path)["sync_s"] is not None
    spans = [e for evs in trace_reduce.named_events(path, "bench_p").values()
             for e in evs]
    assert [n for n, _s, _d in spans] == ["bench_post"] * 2
    assert all(d > 0 for _n, _s, d in spans)
    assert trace_reduce.named_events(path, "no_such_span") == {}
