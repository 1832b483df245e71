"""Fragment result cache (Presto@Meta VLDB'23 §4.2 reproduction):
semantic plan fingerprints + per-table version invalidation, the
memory-bounded worker-side result store, cache-affinity scheduling,
observability through task stats / EXPLAIN ANALYZE, and the re-bound
ordered-merge collect.

The invalidation contract under test: a cache key embeds every scanned
table's monotonic version, so a write makes every stale entry
structurally unreachable — the cache can serve a wrong answer only if
the fingerprint machinery itself is wrong, never by forgetting to purge.
"""

import os
import sys
import threading
import time

import jax.numpy as jnp
import pytest

from presto_tpu.cache import (AffinityRouter, FragmentResultCache,
                              rendezvous_pick)
from presto_tpu.config import TransportConfig
from presto_tpu.connectors import MemoryConnector, TpchConnector
from presto_tpu.exec.engine import LocalEngine
from presto_tpu.exec.split_executor import SplitExecutor
from presto_tpu.plan.fingerprint import fragment_cache_key, plan_fingerprint
from presto_tpu.server.cluster import TpuCluster, bounded_merge
from presto_tpu.testing import FaultInjector, FaultSpec
from presto_tpu.utils.tracing import TRACER, trace_scope

CACHE_ON = {"fragment_result_cache_enabled": "true"}


@pytest.fixture
def exec_counter(monkeypatch):
    """Counts real fragment executions — a cache hit must NOT reach
    SplitExecutor.execute."""
    counter = {"n": 0}
    orig = SplitExecutor.execute

    def counted(self, plan):
        counter["n"] += 1
        return orig(self, plan)

    monkeypatch.setattr(SplitExecutor, "execute", counted)
    return counter


# ---------------------------------------------------------------- store
def _entry(n_bytes: int):
    """A fake cached 'page list' — the store only needs pytree leaves
    with .nbytes."""
    return [jnp.zeros(n_bytes, dtype=jnp.int8)]


def test_store_hit_miss_and_lru_eviction_respects_budget():
    store = FragmentResultCache(budget_bytes=4096, max_entry_bytes=4096)
    for i in range(4):
        assert store.put(f"k{i}", _entry(1024))
    assert store.stats()["bytes"] <= 4096
    # touch k0 so it is MRU; k1 becomes the eviction victim
    assert store.get("k0") is not None
    assert store.put("k4", _entry(1024))
    st = store.stats()
    assert st["bytes"] <= 4096, "byte budget held after eviction"
    assert st["evictions"] >= 1
    assert store.get("k1") is None, "LRU entry evicted"
    assert store.get("k0") is not None, "recently-used entry survived"
    hits, misses = st["hits"], st["misses"]
    assert store.stats()["hits"] > 0 and misses >= 0 and hits >= 1


def test_store_refuses_oversized_entry():
    store = FragmentResultCache(budget_bytes=4096, max_entry_bytes=2048)
    assert store.put("small", _entry(1024))
    assert not store.put("huge", _entry(4096)), \
        "one oversized entry must not wipe the cache"
    assert store.get("small") is not None
    assert len(store) == 1


def test_store_is_thread_safe_under_contention():
    store = FragmentResultCache(budget_bytes=64 * 1024)
    errors = []

    def worker(tid):
        try:
            for i in range(50):
                store.put(f"k{tid}-{i % 7}", _entry(512))
                store.get(f"k{(tid + 1) % 4}-{i % 7}")
        except Exception as e:    # noqa: BLE001 — the assertion payload
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert store.stats()["bytes"] <= 64 * 1024


# ---------------------------------------------------------- fingerprint
def test_fingerprint_invariant_to_node_ids_and_aliases():
    conn = TpchConnector(0.01)
    eng = LocalEngine(conn)
    sql = "SELECT n_name FROM nation WHERE n_nationkey < 5"
    # two plannings of the same SQL allocate fresh plan-node ids
    p1 = eng.plan_sql(sql)
    p2 = eng.plan_sql(sql)
    # symbol renaming: aliases change output_names, not semantics
    p3 = eng.plan_sql(
        "SELECT n_name AS renamed FROM nation WHERE n_nationkey < 5")
    fp = plan_fingerprint(p1)
    assert plan_fingerprint(p2) == fp, "node ids must not leak in"
    assert plan_fingerprint(p3) == fp, "symbol names must not leak in"
    # a changed predicate constant is a DIFFERENT computation
    p4 = eng.plan_sql("SELECT n_name FROM nation WHERE n_nationkey < 6")
    assert plan_fingerprint(p4) != fp


def test_cache_key_embeds_table_versions_and_splits():
    conn = TpchConnector(0.01)
    eng = LocalEngine(conn)
    plan = eng.plan_sql("SELECT count(*) FROM nation")
    splits = {"nation": [(0, 2)]}
    k0 = fragment_cache_key(plan, [("nation", 0)], splits)
    k1 = fragment_cache_key(plan, [("nation", 1)], splits)
    assert k0 != k1, "a version bump must unreach the old key"
    k2 = fragment_cache_key(plan, [("nation", 0)], {"nation": [(1, 2)]})
    assert k2 != k0, "different split = different partial result"
    assert fragment_cache_key(plan, [("nation", 0)], splits) == k0


# ------------------------------------------------------------- affinity
def test_rendezvous_and_affinity_router():
    workers = [f"http://w{i}" for i in range(4)]
    picked = rendezvous_pick("fp-abc", workers)
    assert picked in workers
    assert rendezvous_pick("fp-abc", workers) == picked, "deterministic"
    assert rendezvous_pick("fp-abc", list(reversed(workers))) == picked

    router = AffinityRouter()
    assert router.pick("fp", []) is None
    router.record("fp", workers[2])
    assert router.pick("fp", workers) == workers[2], "observed holder"
    live = [w for w in workers if w != workers[2]]
    fallback = router.pick("fp", live)
    assert fallback in live, "dead holder -> rendezvous among live"
    assert fallback == rendezvous_pick("fp", live)


# -------------------------------------------------------------- cluster
@pytest.fixture(scope="module")
def cached_cluster():
    c = TpuCluster(TpchConnector(0.01), n_workers=2,
                   session_properties=dict(CACHE_ON))
    yield c
    c.stop()


def test_second_execution_is_a_cache_hit(cached_cluster, exec_counter):
    c = cached_cluster
    sql = ("SELECT l_returnflag, count(*), sum(l_quantity) FROM lineitem "
           "GROUP BY l_returnflag ORDER BY l_returnflag")
    r1 = c.execute_sql(sql)
    first_run = exec_counter["n"]
    assert first_run > 0
    r2 = c.execute_sql(sql, _capture=True)
    second_run = exec_counter["n"] - first_run
    assert r2 == r1, "cached pages replay to identical rows"
    assert second_run < first_run, \
        "leaf fragments must be served from cache, not re-executed"
    hits = [int(info["stats"]["runtimeStats"]
                ["fragmentResultCacheHit"]["sum"])
            for _fid, info in c.last_task_infos
            if "fragmentResultCacheHit"
            in (info["stats"].get("runtimeStats") or {})]
    assert sum(hits) >= 1, "per-task cache-hit flag surfaced in stats"


def test_cache_stats_in_explain_analyze(cached_cluster):
    text = cached_cluster.explain_analyze_sql(
        "SELECT count(*) FROM orders")
    cached_cluster.explain_analyze_sql("SELECT count(*) FROM orders")
    text = cached_cluster.explain_analyze_sql(
        "SELECT count(*) FROM orders")
    assert "Result cache:" in text
    assert "hits=" in text and "misses=" in text \
        and "evictions=" in text and "bytes=" in text
    # by the third run the leaf tasks are warm
    line = [ln for ln in text.splitlines()
            if ln.startswith("Result cache:")][0]
    served = int(line.split(":")[1].strip().split("/")[0])
    assert served >= 1


def test_insert_bumps_version_and_is_never_stale():
    mem = MemoryConnector(fallback=TpchConnector(0.01))
    eng = LocalEngine(mem)
    eng.execute_sql("CREATE TABLE acct (k varchar, v bigint)")
    eng.execute_sql("INSERT INTO acct VALUES ('a', 1), ('b', 2)")
    c = TpuCluster(mem, n_workers=2,
                   session_properties=dict(CACHE_ON))
    try:
        sql = "SELECT sum(v) FROM acct"
        v_before = mem.table_version("acct")
        assert c.execute_sql(sql) == [(3,)]
        assert c.execute_sql(sql) == [(3,)]          # warm: served cached
        c.execute_sql("INSERT INTO acct VALUES ('c', 10)")
        assert mem.table_version("acct") > v_before, \
            "every write bumps the table version"
        # the old key is unreachable — the fresh row MUST be visible
        assert c.execute_sql(sql) == [(13,)]
        c.execute_sql("INSERT INTO acct VALUES ('d', 100)")
        assert c.execute_sql(sql) == [(113,)]
    finally:
        c.stop()


def test_killed_worker_cache_degrades_to_misses_not_errors():
    """Chaos case (testing/faults.py): warm both workers' caches, kill
    one worker's transport, and re-run — the lost cache must surface as
    re-execution on the survivors, never as an error or a wrong row."""
    transport = TransportConfig(
        retry_base_backoff_s=0.01, retry_max_backoff_s=0.1,
        retry_budget_s=2.0, breaker_failure_threshold=2,
        breaker_cooldown_s=0.2, probe_timeout_s=1.0)
    c = TpuCluster(TpchConnector(0.01), n_workers=2,
                   session_properties=dict(CACHE_ON),
                   transport_config=transport)
    try:
        sql = ("SELECT n_regionkey, count(*) FROM nation "
               "GROUP BY n_regionkey ORDER BY n_regionkey")
        baseline = c.execute_sql(sql)
        assert c.execute_sql(sql) == baseline        # caches warm
        victim = c.all_worker_uris[0]
        victim_host = victim.split("://", 1)[1]
        inj = FaultInjector(seed=1,
                            spec=FaultSpec(kill_after={victim_host: 0}))
        c.http.fault_injector = inj
        try:
            got = c.execute_sql(sql)
        finally:
            c.http.fault_injector = None
        assert got == baseline, \
            "lost cache re-executes on survivors with identical rows"
        # the dead worker was excluded, then re-admitted after revival
        assert victim in c.dead
        inj.revive(victim_host)
        time.sleep(0.3)
        c.check_workers()
        assert victim not in c.dead
        assert c.execute_sql(sql) == baseline
    finally:
        c.stop()


# -------------------------------------------------------- bounded merge
class _Key:
    def __init__(self, row):
        self.row = row

    def __lt__(self, other):
        return self.row[0] < other.row[0]


# one slot a queue: an end marker competes with the batches for it
@pytest.mark.parametrize("queue_pages", [1, 2])
def test_bounded_merge_sorts_with_bounded_in_flight(queue_pages):
    k = 4
    per_stream = 40

    def source(s):
        def batches():
            # pre-sorted runs, one small batch at a time
            for b in range(per_stream):
                yield [((s + k * b),)]
        return batches

    rows, high = bounded_merge([source(s) for s in range(k)], key=_Key,
                               queue_pages=queue_pages)
    assert [r[0] for r in rows] == list(range(k * per_stream))
    assert high <= k * (queue_pages + 2), \
        f"in-flight batches must stay bounded, saw {high}"


def test_bounded_merge_propagates_producer_failure():
    def ok():
        for i in range(100):
            yield [(i,)]

    def boom():
        yield [(0,)]
        raise ValueError("stream died")

    with pytest.raises(ValueError, match="stream died"):
        bounded_merge([lambda: ok(), lambda: boom()], key=_Key,
                      queue_pages=2)


def test_bounded_merge_failure_wakes_a_merge_blocked_on_a_sibling():
    """The merging thread blocks on stream 0, whose producer sleeps 5 s;
    stream 1's producer fails meanwhile. Its failure marker wakes the
    merge, which raises the real error without waiting out the sleep."""
    release = threading.Event()

    def sleepy():
        release.wait(5)
        yield [(0,)]

    def boom():
        time.sleep(0.05)         # the merge is blocked on stream 0 by now
        raise ValueError("stream died")
        yield

    t0 = time.perf_counter()
    try:
        with trace_scope("merge-woken"), \
                pytest.raises(ValueError, match="stream died"):
            bounded_merge([sleepy, boom], key=_Key)
        took = time.perf_counter() - t0
    finally:
        release.set()
    assert took < 2.0, took
    merge, = [s for s in TRACER.get("merge-woken") if s.name == "root_merge"]
    assert merge.attributes["timeouts"] == 0     # woken, not timed out


def test_bounded_merge_never_takes_a_failure_for_an_end():
    """A failing stream beside streams that finish always raises, whatever
    the interleaving: an end marker read after the failure, or a failure
    marker that found its queue full, never ends the merge with the rows
    taken so far. Thread switches are forced often, and every tenth merge
    has more producers than the machine has cores."""
    def ok(n, s):
        def batches():
            for i in range(n):
                yield [(2 * i + s,)]
        return batches

    def boom(n):
        def batches():
            for i in range(n):
                yield [(2 * i + 1,)]
            raise ValueError("stream died")
        return batches

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for rep in range(240):
            n_ok, n_boom, pages = rep % 5, (rep // 5) % 4, 1 + (rep // 20) % 3
            siblings = (os.cpu_count() or 1) + 1 if rep % 10 == 9 else 1
            sources = [ok(n_ok, s) for s in range(siblings)] + [boom(n_boom)]
            if rep % 2:
                sources.reverse()
            with pytest.raises(ValueError, match="stream died"):
                bounded_merge(sources, key=_Key, queue_pages=pages)
    finally:
        sys.setswitchinterval(interval)


@pytest.mark.parametrize("streams", [
    [[]], [[], []], [[[]]], [[[], []], []], [[[(1,)], []], [[], [(0,)]]],
    [[[(0,)], [(2,)]], [[]], []],
], ids=["one-empty", "two-empty", "one-empty-batch", "empty-batches",
        "empty-batches-between-rows", "rows-beside-empty"])
def test_bounded_merge_ends_empty_streams_without_timeouts(streams):
    with trace_scope("merge-empty"):
        rows, high = bounded_merge(
            [(lambda b=b: iter(b)) for b in streams], key=_Key)
    want = sorted(r for b in streams for batch in b for r in batch)
    assert rows == want
    assert high <= len(streams) * (4 + 2)
    merge = [s for s in TRACER.get("merge-empty") if s.name == "root_merge"]
    assert merge and all(m.attributes["timeouts"] == 0 for m in merge)


def test_cluster_merge_records_bounded_high_water(cached_cluster):
    c = cached_cluster
    rows = c.execute_sql(
        "SELECT l_orderkey, l_linenumber FROM lineitem "
        "ORDER BY l_orderkey, l_linenumber")
    assert rows == sorted(rows)
    high = c.last_merge_inflight_high
    assert high >= 1
    assert high <= len(c.workers) * (TpuCluster.MERGE_QUEUE_PAGES + 2)
