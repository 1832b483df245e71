"""A split scan's columns stay on the device (connectors/scan_cache.py,
`HostTable.split`, `SplitExecutor._scan_page`): the worker's second scan
of (table, split, column, capacity) moves no bytes; what is kept dies
with the table instance that keeps it and stays under one budget. CPU:
counts, bytes and answers, never rates."""

import itertools
import threading

import numpy as np
import pytest

from presto_tpu.connectors import MemoryConnector, TpchConnector
from presto_tpu.connectors import scan_cache, tpch
from presto_tpu.connectors.scan_cache import ScanCache
from presto_tpu.data.column import page_nbytes
from presto_tpu.exec.engine import LocalEngine
from presto_tpu.exec.program_cache import ProgramCache
from presto_tpu.exec.split_executor import SplitExecutor
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.types import BIGINT, DOUBLE, VARCHAR
from presto_tpu.utils.tracing import TRACER, trace_scope

from tests.tpch_queries import QUERIES

SF = 0.01
TABLES = ("lineitem", "orders", "customer")


@pytest.fixture
def ledger(monkeypatch):
    """A ledger of the test's own (a budget nothing here reaches), and
    the shared generated tables as a process finds them: nothing kept."""
    for name in TABLES:
        t = TpchConnector(SF).table(name)
        t.__dict__.pop("_dev_page_cache", None)
        t.__dict__.pop("_split_views", None)
    mine = ScanCache(budget=1 << 30)
    monkeypatch.setattr(tpch, "SCAN_CACHE", mine)
    return mine


def _counts():
    scans = REGISTRY.get("presto_tpu_scan_cache_total")
    return scans.value(result="hit"), scans.value(result="miss")


_TRACES = itertools.count()


def _scan(connector, plan, splits, programs=None):
    """(rows, the `upload` spans' attributes) of `plan` on a new
    SplitExecutor, as every task of a worker builds one."""
    ex = SplitExecutor(connector, programs=programs)
    ex.set_splits(splits)
    trace = f"scan-cache-{next(_TRACES)}"
    with trace_scope(trace, ""):
        rows = ex.execute(plan).to_pylist()
    return rows, [s.attributes for s in TRACER.get(trace)
                  if s.name == "upload"]


def _memory_table(n=1000):
    mem = MemoryConnector()
    mem.create("t", [("k", BIGINT), ("v", DOUBLE), ("w", VARCHAR)])
    mem.append_rows("t", [(i, i / 4.0, f"w{i % 7}") for i in range(n)])
    return mem


def test_a_second_executor_finds_every_column_resident(ledger):
    connector = TpchConnector(SF)
    plan = LocalEngine(connector).plan_sql(
        "select sum(l_quantity), max(l_discount), min(l_tax) from lineitem")
    splits = {"lineitem": [(1, 2)]}
    programs = ProgramCache()
    hits, misses = _counts()
    first, (up1,) = _scan(connector, plan, splits, programs)
    assert _counts() == (hits, misses + 3)
    assert up1["table"] == "lineitem" and up1["bytes"] > 100_000
    assert "resident" not in up1
    again, (up2,) = _scan(connector, plan, splits, programs)
    assert _counts() == (hits + 3, misses + 3)
    assert again == first
    # nothing moved, not even the page's row count
    assert up2["bytes"] == 0 and up2["resident"] == up1["bytes"]
    view = connector.table("lineitem", part=1, num_parts=2)
    assert view is connector.table("lineitem", 1, 2)
    page = view.page(["l_quantity", "l_discount", "l_tax"],
                     capacity=next(iter(view._dev_page_cache))[1])
    assert page_nbytes(page) == up2["resident"]
    assert ledger.bytes == up1["bytes"] - 4      # the columns, not the count
    # the other split of the table is another view with columns of its own
    _rows, (up3,) = _scan(connector, plan, {"lineitem": [(0, 2)]}, programs)
    assert up3["bytes"] == up1["bytes"] and "resident" not in up3


def test_two_column_subsets_share_entries(ledger):
    connector = TpchConnector(SF)
    engine = LocalEngine(connector)
    splits = {"lineitem": [(0, 2)]}
    _rows, (a,) = _scan(connector, engine.plan_sql(
        "select sum(l_quantity), sum(l_tax) from lineitem"), splits)
    hits, misses = _counts()
    _rows, (b,) = _scan(connector, engine.plan_sql(
        "select sum(l_tax), sum(l_discount) from lineitem"), splits)
    assert _counts() == (hits + 1, misses + 1)      # l_tax was there
    assert b["resident"] + b["bytes"] == a["bytes"]
    assert b["bytes"] == (a["bytes"] - 4) // 2
    view = connector.table("lineitem", 0, 2)
    assert sorted(c for c, _cap in view._dev_page_cache) == [
        "l_discount", "l_quantity", "l_tax"]


@pytest.mark.parametrize("q", [6, 1, 3])
def test_answers_are_equal_cold_warm_and_with_nothing_kept(ledger, q):
    connector = TpchConnector(SF)
    plan = LocalEngine(connector).plan_sql(QUERIES[q])
    splits = {t: [(0, 2)] for t in TABLES}
    programs = ProgramCache()
    cold, ups = _scan(connector, plan, splits, programs)
    assert cold and all(u["bytes"] > 0 for u in ups)
    warm, ups = _scan(connector, plan, splits, programs)
    assert [u["bytes"] for u in ups if "table" in u] == [0] * len(ups)
    assert warm == cold
    ledger.budget = 0               # nothing fits: every scan moves it all
    for t in TABLES:
        connector.table(t, 0, 2).__dict__.pop("_dev_page_cache", None)
    for _ in range(2):
        none, ups = _scan(connector, plan, splits, programs)
        # (but for the four bytes of a page's row count)
        assert none == cold and all(
            u["bytes"] > 4 and u.get("resident", 0) <= 4 for u in ups)
    assert ledger.bytes == 0


def test_a_write_is_seen_by_the_next_scan_and_starts_cold(ledger):
    mem = _memory_table(1000)
    plan = LocalEngine(mem).plan_sql("select count(*), sum(v) from t")
    splits = {"t": [(1, 2)]}
    _scan(mem, plan, splits)
    rows, (up,) = _scan(mem, plan, splits)
    assert rows == [(500, sum(i / 4.0 for i in range(500, 1000)))]
    assert up["bytes"] == 0
    old = mem.table("t", 1, 2)
    mem.append_rows("t", [(i, 1.0, "new") for i in range(1000, 1200)])
    assert mem.table("t", 1, 2) is not old       # a new version, new views
    hits, misses = _counts()
    rows, (up,) = _scan(mem, plan, splits)
    assert rows == [(600, sum(i / 4.0 for i in range(600, 1000)) + 200.0)]
    assert up["bytes"] > 0 and "resident" not in up
    assert _counts() == (hits, misses + 1)
    # the old version's columns went with it (it is still alive here)
    del old
    _scan(mem, plan, {"t": [(0, 2)]})
    assert ledger.bytes == sum(
        page_nbytes(c) for p in (0, 1)
        for c in mem.table("t", p, 2)._dev_page_cache.values())


def test_the_bound_evicts_the_least_recently_scanned_column(ledger):
    mem = _memory_table(1000)
    engine = LocalEngine(mem)
    splits = {"t": [(0, 2)]}
    k = engine.plan_sql("select sum(k) from t")
    v = engine.plan_sql("select sum(v) from t")
    kv = engine.plan_sql("select sum(k), sum(v) from t")
    _rows, (up,) = _scan(mem, k, splits)
    column = up["bytes"] - 4            # slots of 8 + 1 bytes
    assert column % 9 == 0 and column >= 500 * 9
    ledger.budget = 2 * column + 100    # room for two columns, not three
    _scan(mem, v, splits)
    _scan(mem, k, splits)               # k is the most recent now
    evictions = REGISTRY.get("presto_tpu_scan_cache_evictions_total")
    before = evictions.value()
    rows, (up,) = _scan(mem, engine.plan_sql("select count(w) from t"),
                        splits)
    assert rows == [(500,)] and up["evicted"] == column
    assert evictions.value() == before + 1
    view = mem.table("t", 0, 2)
    assert sorted(c for c, _cap in view._dev_page_cache) == ["k", "w"]
    assert ledger.bytes <= ledger.budget
    assert REGISTRY.get("presto_tpu_scan_cache_resident_bytes"
                        ).value() == ledger.bytes
    # the evicted column is put up again, and the answer is exact
    rows, (up,) = _scan(mem, kv, splits)
    assert rows == [(sum(range(500)), sum(i / 4.0 for i in range(500)))]
    assert up["resident"] == column + 4 and up["bytes"] == column
    assert ledger.bytes <= ledger.budget


def test_a_column_larger_than_the_bound_is_never_kept(ledger):
    mem = _memory_table(1000)
    plan = LocalEngine(mem).plan_sql("select sum(k) from t")
    ledger.budget = 500 * 9 - 1
    for _ in range(2):
        rows, (up,) = _scan(mem, plan, {"t": [(0, 2)]})
        assert rows == [(sum(range(500)),)]
        assert up["bytes"] >= 500 * 9 and "evicted" not in up
    assert not mem.table("t", 0, 2)._dev_page_cache
    assert ledger.bytes == 0


def test_a_row_slice_run_and_a_multi_part_task_are_not_kept(ledger):
    mem = _memory_table(1000)
    plan = LocalEngine(mem).plan_sql("select count(*), sum(k) from t")
    # several parts in one task: concatenated for the scan alone
    for _ in range(2):
        rows, (up,) = _scan(mem, plan, {"t": [(0, 4), (2, 4)]})
        assert rows == [(500, sum(range(250)) + sum(range(500, 750)))]
        assert up["bytes"] > 0 and "resident" not in up
    # a streaming scan run: a throwaway window of the split
    runs = list(mem.scan_runs("t", 100, part=0, num_parts=2))
    assert len(runs) == 5 and not any(r.keeps_device_columns for r in runs)
    ex = SplitExecutor(mem)
    for _ in range(2):
        ex.set_split_tables({"t": runs[1]})
        trace = f"scan-cache-{next(_TRACES)}"
        with trace_scope(trace, ""):
            assert ex.execute(plan).to_pylist() == [
                (100, sum(range(100, 200)))]
        (up,) = [s.attributes for s in TRACER.get(trace)
                 if s.name == "upload"]
        assert up["bytes"] > 0 and "resident" not in up
    assert ledger.bytes == 0
    assert not mem.tables["t"].__dict__.get("_dev_page_cache")


def test_two_threads_scanning_one_split_get_equal_pages(ledger):
    mem = _memory_table(4000)
    view = mem.table("t", 1, 2)
    barrier = threading.Barrier(2)
    pages = []

    def scan():
        barrier.wait(timeout=60)
        pages.append(mem.table("t", 1, 2).page(["k", "v", "w"], 2048))

    threads = [threading.Thread(target=scan) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    a, b = pages
    assert a.to_pylist() == b.to_pylist() and len(a.to_pylist()) == 2000
    assert a.to_pylist()[0] == (2000, 500.0, "w5")
    # one view, one entry a column, counted once whoever put it last
    assert mem.table("t", 1, 2) is view and len(view._dev_page_cache) == 3
    assert ledger.bytes == sum(
        page_nbytes(c) for c in view._dev_page_cache.values())


def test_many_threads_under_a_tight_budget_keep_the_ledger_true(ledger):
    """More threads than cores scan both splits' columns while the
    budget holds three of the six: keeps, hits and evictions race, every
    page is still its own split's rows, and afterwards the ledger counts
    exactly what the views keep, under the budget."""
    import sys
    mem = _memory_table(4000)
    column = page_nbytes(mem.table("t", 0, 2).page(["k"], 2048).columns[0])
    mem.table("t", 0, 2).__dict__.pop("_dev_page_cache")
    ledger.budget = 3 * column + 8     # the popped column is swept
    wrong, rounds = [], 40
    barrier = threading.Barrier(32)

    def scan(i):
        barrier.wait(timeout=60)
        for r in range(rounds):
            part = (i + r) % 2
            cols = [["k", "v"], ["v", "w"], ["w", "k"], ["k"]][(i + r) % 4]
            page = mem.table("t", part, 2).page(cols, 2048)
            lo = 2000 * part
            for c, col in zip(cols, page.columns):
                got = np.asarray(col.values)[:2000]
                want = {"k": np.arange(lo, lo + 2000),
                        "v": np.arange(lo, lo + 2000) / 4.0}.get(c)
                if want is not None and not np.array_equal(got, want):
                    wrong.append((i, r, part, c))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=scan, args=(i,))
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not wrong
    kept = [c for p in (0, 1)
            for c in mem.table("t", p, 2)._dev_page_cache.values()]
    assert ledger.bytes == sum(page_nbytes(c) for c in kept)
    assert 0 < ledger.bytes <= ledger.budget and len(kept) <= 3


def test_the_budget_is_a_share_of_what_the_device_reports(monkeypatch):
    """No knob: a constant share of `memory_stats()["bytes_limit"]`, and
    a constant byte count where the backend reports none (this CPU)."""
    import jax

    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    assert jax.local_devices()[0].memory_stats() is None
    assert ScanCache().budget == scan_cache.SCAN_CACHE_BYTES == 1 << 30
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [Device({"bytes_limit": 16_000_000_000})])
    assert scan_cache.SCAN_CACHE_DEVICE_SHARE == 0.5
    assert ScanCache().budget == 8_000_000_000
