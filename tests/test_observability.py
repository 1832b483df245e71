"""Tracing spans, query events and resource-group admission control
(reference: spi/tracing SimpleTracer, spi/eventlistener ->
EventListenerManager, execution/resourceGroups/InternalResourceGroup)."""

import threading
import time

import pytest

from presto_tpu.connectors import TpchConnector
from presto_tpu.exec import LocalEngine
from presto_tpu.admission.groups import (
    QueryQueueFull, ResourceGroup, ResourceGroupManager, Selector,
)
from presto_tpu.utils import EVENTS, TRACER, QueryEvent


def test_events_and_spans():
    seen = []
    EVENTS.register(seen.append)
    eng = LocalEngine(TpchConnector(0.01))
    eng.execute_sql("select count(*) from region")
    kinds = [e.kind for e in seen]
    assert "created" in kinds and "completed" in kinds
    done = [e for e in seen if e.kind == "completed"][-1]
    assert done.rows == 1 and done.wall_s is not None
    spans = TRACER.get(done.query_id)
    names = [s.name for s in spans]
    assert "plan" in names and "execute" in names
    assert all(s.duration_s is not None for s in spans)
    assert "execute" in TRACER.render(done.query_id)


def test_failed_query_event():
    seen = []
    EVENTS.register(seen.append)
    eng = LocalEngine(TpchConnector(0.01))
    with pytest.raises(Exception):
        eng.execute_sql("select no_such from region")
    assert any(e.kind == "failed" and e.error for e in seen)


def test_resource_group_concurrency_and_queue():
    g = ResourceGroup("etl", hard_concurrency=1, max_queued=1)
    mgr = ResourceGroupManager(
        [g, ResourceGroup("global")],
        [Selector("etl", user_regex="etl_.*"), Selector("global")])
    assert mgr.select(user="etl_job").name == "etl"
    assert mgr.select(user="alice").name == "global"

    order = []
    s1 = mgr.select(user="etl_x").acquire()
    done = threading.Event()

    def second():
        with mgr.select(user="etl_y").acquire(timeout_s=10):
            order.append("second-ran")
        done.set()

    t = threading.Thread(target=second)
    t.start()
    time.sleep(0.2)
    assert not done.is_set()          # queued behind the held slot
    # a third submission exceeds max_queued -> QUERY_QUEUE_FULL
    with pytest.raises(QueryQueueFull):
        mgr.select(user="etl_z").acquire(timeout_s=0.1)
    s1.__exit__(None, None, None)     # release the slot
    t.join(timeout=10)
    assert order == ["second-ran"]
    assert g.stats["admitted"] == 2 and g.stats["rejected"] == 1


def test_resource_group_run_or_reject():
    """max_queued=0 means run-or-reject: free slots admit immediately."""
    g = ResourceGroup("ror", hard_concurrency=2, max_queued=0)
    s1 = g.acquire()
    s2 = g.acquire()
    with pytest.raises(QueryQueueFull):
        g.acquire(timeout_s=0.1)
    s1.__exit__(None, None, None)
    s2.__exit__(None, None, None)
    assert g.stats["admitted"] == 2 and g.stats["rejected"] == 1


def test_tracer_bounded():
    from presto_tpu.utils import Tracer
    t = Tracer(max_traces=4)
    for i in range(10):
        with t.span(f"q{i}", "x"):
            pass
    assert len(t.spans) == 4 and "q9" in t.spans and "q0" not in t.spans
