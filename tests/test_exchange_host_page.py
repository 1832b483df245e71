"""A page that exists only to cross the exchange has a host form: numpy
arrays and an `np.int32` row count, never a `jax.Array`. The producer's
partitions (`select_page_host`) and the consumer's decoded pages
(`decode_pages`) are such pages; the consumer's fuse
(`concat_pages_host`) reads them for nothing and puts the one fused page
on the device; the wire frames are, byte for byte, what the device-built
pages of before gave. What a jitted island takes is a device page, always:
a numpy leaf handed to `jax.jit` would be uploaded again on every call.

A page's form is the type of its arrays (`device_leaves(page) == 0` says
host). CPU: forms, bytes and counts, never a rate."""

import json
import os
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.connectors import TpchConnector
from presto_tpu.data import column as column_mod
from presto_tpu.data.column import (
    Column, Decimal128Column, NestedColumn, Page, StringDict,
    bucket_capacity, concat_pages_host, device_leaves, page_to_host,
    select_page_host,
)
from presto_tpu.exec.split_executor import SplitExecutor
from presto_tpu.protocol import serde
from presto_tpu.protocol import structs as S
from presto_tpu.protocol.exchange_client import PageStream, decode_pages
from presto_tpu.protocol.serde import (
    decode_serialized_page, encode_serialized_page, page_to_wire_blocks,
    wire_blocks_to_page,
)
from presto_tpu.server import TpuWorkerServer
from presto_tpu.server.buffers import OutputBufferManager
from presto_tpu.server.cluster import TpuCluster
from presto_tpu.server.statement import StatementServer, run_statement
from presto_tpu.server.task_manager import (
    Task, TpuTaskManager, _hash_partition_ids,
)
from presto_tpu.types import (
    BIGINT, BOOLEAN, DATE, DOUBLE, VARCHAR, ArrayType, DecimalType,
)
from presto_tpu.utils.tracing import TRACER, trace_scope
from tests.protocol_fixtures import fragment, task_update_request, var

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare  # noqa: E402
import qgen  # noqa: E402
import run as bench_run  # noqa: E402

KINDS = ["bigint", "double", "boolean", "date", "short_decimal",
         "varchar_shared_dictionary", "varchar_dictionary_a_page",
         "decimal128", "array"]
SHORT_DECIMAL = DecimalType(12, 2)
LONG_DECIMAL = DecimalType(38, 4)
ARRAY = ArrayType(BIGINT)
#: rows a page: three pages, the middle one within one bucket of the first
ROWS = (300, 41, 7)
WORDS = [f"Customer#{i:09d}" for i in range(64)] + ["", "zebra"]
SHARED = StringDict(sorted(WORDS))


def _column(kind: str, rng, n: int):
    """(type, a device column of n rows of `kind`, NULLs among them)."""
    nulls = rng.random(n) < 0.2
    nulls[:2] = [False, True] if n > 1 else False
    if kind == "bigint":
        return BIGINT, Column.from_numpy(
            rng.integers(-2 ** 40, 2 ** 40, n), BIGINT, nulls=nulls)
    if kind == "double":
        v = rng.standard_normal(n)
        v[0] = -0.0
        return DOUBLE, Column.from_numpy(v, DOUBLE, nulls=nulls)
    if kind == "boolean":
        return BOOLEAN, Column.from_numpy(
            rng.random(n) < 0.5, BOOLEAN, nulls=nulls)
    if kind == "date":
        return DATE, Column.from_numpy(
            rng.integers(8000, 10600, n).astype(np.int32), DATE,
            nulls=nulls)
    if kind == "short_decimal":
        return SHORT_DECIMAL, Column.from_numpy(
            rng.integers(-10 ** 11, 10 ** 11, n), SHORT_DECIMAL,
            nulls=nulls)
    if kind == "varchar_shared_dictionary":
        # a table's column: one dictionary object, most words unused
        return VARCHAR, Column.from_numpy(
            rng.integers(0, len(SHARED), n).astype(np.int32), VARCHAR,
            nulls=nulls, dictionary=SHARED)
    if kind == "varchar_dictionary_a_page":
        words = [None if nl else WORDS[i] for nl, i in
                 zip(nulls, rng.integers(0, len(WORDS), n))]
        return VARCHAR, Column.from_strings(words)
    if kind == "decimal128":
        ints = [None if nl else int(v) * 10 ** 20 + int(v)
                for nl, v in zip(nulls, rng.integers(-10 ** 15, 10 ** 15, n))]
        return LONG_DECIMAL, Decimal128Column.from_unscaled_ints(
            ints, LONG_DECIMAL)
    assert kind == "array"
    vals = [None if nl else [int(x) for x in rng.integers(0, 99, k)]
            for nl, k in zip(nulls, rng.integers(0, 4, n))]
    return ARRAY, NestedColumn.from_pylist(vals, ARRAY)


def make_pages(kind: str, seed: int = 7):
    """([key type, the kind's type], three device pages): a BIGINT key
    without NULLs beside the column under test."""
    rng = np.random.default_rng(seed)
    pages, t = [], None
    for n in ROWS:
        key = Column.from_numpy(rng.integers(0, 50, n), BIGINT)
        t, col = _column(kind, rng, n)
        pages.append(Page.from_columns([key, col], n, ("k", "v")))
    return [BIGINT, t], pages


def as_host(page: Page) -> Page:
    """The same page in its host form: every array the numpy copy."""
    host = jax.tree_util.tree_map(np.asarray, page)
    return Page.host_from_columns(host.columns, int(page.num_rows),
                                  page.names)


def frame_of(page: Page) -> bytes:
    return encode_serialized_page(page_to_wire_blocks(page),
                                  checksummed=True)


def all_on_device(tree) -> bool:
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and device_leaves(tree) == len(leaves)


def dictionaries(page: Page):
    return [None if c.dictionary is None else c.dictionary.words
            for c in page.columns]


def assert_pages_equal(a: Page, b: Page):
    """Leaf by leaf at the pages' capacity, padding included, and word
    by word: what a program would be handed."""
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)
    assert dictionaries(a) == dictionaries(b)
    assert a.names == b.names
    assert a.to_pylist() == b.to_pylist()


def parent_select_page_host(page: Page, idx: np.ndarray) -> Page:
    """`select_page_host` as it stood before this file: the partition
    padded to a bucket and put on the device leaf by leaf."""
    n = len(idx)
    cap = bucket_capacity(max(n, 1))
    pad = cap - n
    cols = []
    for c in page.columns:
        if isinstance(c, Decimal128Column):
            cols.append(c.from_lanes([
                jnp.asarray(np.pad(np.asarray(lane)[idx], (0, pad),
                                   constant_values=True if li == 4 else 0))
                for li, lane in enumerate(c.row_lanes())]))
        elif isinstance(c, NestedColumn):
            cols.append(NestedColumn(
                jnp.asarray(np.pad(np.asarray(c.starts)[idx], (0, pad))),
                jnp.asarray(np.pad(np.asarray(c.lengths)[idx], (0, pad))),
                jnp.asarray(np.pad(np.asarray(c.nulls)[idx], (0, pad),
                                   constant_values=True)),
                c.children, c.type))
        else:
            v, nl = c.to_numpy(int(page.num_rows))
            cols.append(Column.from_numpy(
                v[idx], c.type, nulls=nl[idx], dictionary=c.dictionary,
                capacity=cap))
    return Page.from_columns(cols, n, page.names)


# -- (i) the forms -----------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_partition_is_a_host_page(kind):
    _types, pages = make_pages(kind)
    page = pages[0]
    assert all_on_device(page)
    page_to_host(page)
    n = int(page.num_rows)
    for idx in (np.arange(0, n, 2), np.arange(n), np.zeros(0, np.int64)):
        part = select_page_host(page, idx)
        assert device_leaves(part) == 0
        assert type(part.num_rows) is np.int32
        assert all(isinstance(a, (np.ndarray, np.generic))
                   for a in jax.tree_util.tree_leaves(part))
        rows = page.to_pylist()
        assert part.to_pylist() == [rows[i] for i in idx]
        # no padding: the wire blocks read [:num_rows] and nothing else
        assert part.capacity == len(idx)


@pytest.mark.parametrize("kind", KINDS)
def test_a_decoded_page_is_a_host_page(kind):
    types, pages = make_pages(kind)
    data = b"".join(frame_of(p) for p in pages)
    before = {form: serde._EXCHANGE_PAGE.value(side="decode", form=form)
              for form in ("host", "device")}
    decoded = decode_pages(data, types)
    assert len(decoded) == len(pages)
    for got, want in zip(decoded, pages):
        assert device_leaves(got) == 0
        assert type(got.num_rows) is np.int32
        assert got.to_pylist() == want.to_pylist()
        # at its rows: the fuse reads [:num_rows] and nothing else
        assert got.capacity == int(want.num_rows)
    assert serde._EXCHANGE_PAGE.value(side="decode", form="host") \
        - before["host"] == len(pages)
    assert serde._EXCHANGE_PAGE.value(side="decode", form="device") \
        == before["device"]
    # the decoder's other caller (exec/spill) still gets a device page
    blocks, n, _off = decode_serialized_page(data, 0)
    assert all_on_device(wire_blocks_to_page(blocks, types, n))


# -- (ii) the frames ---------------------------------------------------------

def _scheme(kind: str, keys):
    layout = [var("k", "bigint"), var("v", "bigint")]
    return S.PartitioningScheme(
        partitioning=S.PartitioningScheme_Partitioning(
            handle=S.PartitioningHandle(connectorHandle={
                "@type": "$remote", "partitioning": kind,
                "function": "HASH"}),
            arguments=[layout[i] for i in keys]),
        outputLayout=layout)


def _routed_frames(page: Page, distribution: str, keys, nbuf: int):
    tm = TpuTaskManager(TpchConnector(0.001))
    task = Task("frames.0.0.0")
    task.buffers = OutputBufferManager([str(b) for b in range(nbuf)])
    task.fragment = S.PlanFragment(
        id="0", root=None, variables=[],
        partitioningScheme=_scheme(distribution, keys))
    page_to_host(page)
    with TRACER.span("frames", "serialize", device_puts=0) as span:
        tm._route_output(task, page)
    frames = [task.buffers.buffers[str(b)].pages for b in range(nbuf)]
    assert all(len(f) == 1 for f in frames)
    return [f[0] for f in frames], span.attributes


@pytest.mark.parametrize("nbuf", [1, 2, 4])
@pytest.mark.parametrize("distribution", [
    "FIXED_HASH_DISTRIBUTION", "FIXED_ARBITRARY_DISTRIBUTION",
    "FIXED_BROADCAST_DISTRIBUTION"])
@pytest.mark.parametrize("kind", KINDS)
def test_frames_are_byte_equal_to_the_device_built_form(kind, distribution,
                                                        nbuf):
    _types, pages = make_pages(kind)
    page = pages[0]
    n = int(page.num_rows)
    # hash on the column under test where it is a flat one, else the key
    keys = (1, 0) if isinstance(page.columns[1], Column) else (0,)
    partitions = serde._EXCHANGE_PAGE.value(side="partition", form="host")
    on_device = serde._EXCHANGE_PAGE.value(side="partition", form="device")
    got, attributes = _routed_frames(page, distribution, keys, nbuf)
    if nbuf == 1 or distribution == "FIXED_BROADCAST_DISTRIBUTION":
        want = [frame_of(page)] * nbuf
        built = 0
    elif distribution == "FIXED_ARBITRARY_DISTRIBUTION":
        want = [frame_of(parent_select_page_host(
            page, np.arange(b, n, nbuf))) for b in range(nbuf)]
        built = nbuf
    else:
        pid = _hash_partition_ids(page, keys, nbuf)
        assert len(set(pid.tolist())) == nbuf
        want = [frame_of(parent_select_page_host(
            page, np.nonzero(pid == b)[0])) for b in range(nbuf)]
        built = nbuf
    assert got == want          # header, checksum and payload
    assert attributes["device_puts"] == 0
    assert serde._EXCHANGE_PAGE.value(side="partition", form="host") \
        - partitions == built
    assert serde._EXCHANGE_PAGE.value(side="partition", form="device") \
        == on_device


# -- (iii) the fuse ----------------------------------------------------------

def _forms(kind: str, source: str):
    """The same three pages as host pages, as device pages and mixed.
    `built`: pages a task made; `decoded`: pages off the wire, their
    string dictionaries as they crossed it (sparse, not compacted)."""
    types, pages = make_pages(kind)
    if source == "built":
        host = [as_host(p) for p in pages]
        device = pages
    else:
        data = b"".join(frame_of(p) for p in pages)
        host = decode_pages(data, types)
        device, off = [], 0
        while off < len(data):
            blocks, n, off = decode_serialized_page(data, off)
            device.append(wire_blocks_to_page(blocks, types, n,
                                              compact_strings=False))
        for h, d in zip(host, device):
            # one decoded dictionary object, whichever form names it
            assert [c.dictionary for c in h.columns] == \
                [c.dictionary for c in d.columns]
    assert all(device_leaves(p) == 0 for p in host)
    assert all(all_on_device(p) for p in device)
    return host, device, [host[0], device[1], host[2]]


@pytest.mark.parametrize("source", ["built", "decoded"])
@pytest.mark.parametrize("kind", KINDS)
def test_the_fuse_gives_one_device_page_whatever_came_in(kind, source,
                                                         monkeypatch):
    host, device, mixed = _forms(kind, source)
    from_device = concat_pages_host(device)
    from_mixed = concat_pages_host(mixed)

    puts, converted = [], []
    device_put, asarray = jax.device_put, jnp.asarray

    def counted_put(tree, *a, **kw):
        puts.append(sum(isinstance(x, (np.ndarray, np.generic))
                        for x in jax.tree_util.tree_leaves(tree)))
        return device_put(tree, *a, **kw)

    def counted_asarray(x, *a, **kw):
        converted.append(x)
        return asarray(x, *a, **kw)

    monkeypatch.setattr(column_mod.jax, "device_put", counted_put)
    monkeypatch.setattr(column_mod.jnp, "asarray", counted_asarray)
    live = len(jax.live_arrays())
    from_host = concat_pages_host(host)
    made = len(jax.live_arrays()) - live
    monkeypatch.undo()

    leaves = len(jax.tree_util.tree_leaves(from_host))
    # one put, of every leaf of the fused page, and no other array made
    assert puts == [leaves] and not converted
    assert made == leaves
    for fused in (from_host, from_device, from_mixed):
        assert all_on_device(fused)
        assert int(fused.num_rows) == sum(ROWS)
        assert fused.capacity == bucket_capacity(sum(ROWS))
    assert_pages_equal(from_host, from_device)
    assert_pages_equal(from_host, from_mixed)
    assert from_host.to_pylist() == [
        r for p in device for r in p.to_pylist()]


# -- (iv) what a program takes -----------------------------------------------

class _RecordedInputs:
    """Every page handed to a task's executor as a remote input."""

    def __init__(self, monkeypatch):
        self.pages = []
        inner = SplitExecutor.set_remote_pages

        def set_remote_pages(ex, by_node):
            self.pages.extend(by_node.values())
            return inner(ex, by_node)

        monkeypatch.setattr(SplitExecutor, "set_remote_pages",
                            set_remote_pages)


def _project_fragment(sf: float) -> S.PlanFragment:
    from tests.test_streaming_worker import project_fragment
    return project_fragment(sf)


def _post(port: int, task_id: str, tur) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/task/{task_id}",
        data=tur.dumps().encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def test_pulled_inputs_are_device_pages():
    """`_pull_one`: the fused page, and the empty page where no producer
    put anything out."""
    sf = 0.001
    srv = TpuWorkerServer(TpchConnector(sf)).start()
    try:
        _post(srv.port, "up.0.0.0", task_update_request(
            _project_fragment(sf), n_splits=3, sf=sf))
        from presto_tpu.plan.nodes import RemoteSourceNode
        tm = TpuTaskManager(TpchConnector(sf))
        task = Task("pull.0.0.0")
        task.remote_splits = {
            "0": [(f"http://127.0.0.1:{srv.port}/v1/task/up.0.0.0", "0")],
            "1": []}
        fused_before = serde._EXCHANGE_PAGE.value(side="fuse", form="host")
        out = {}
        with trace_scope("pull"):
            for node_id in ("0", "1"):
                out.update(tm._pull_remote_inputs(task, RemoteSourceNode(
                    ("revenue",), (DOUBLE,), node_id=node_id,
                    source_fragment_ids=(node_id,))))
        spans = {s.attributes.get("source"): s.attributes
                 for s in TRACER.get("pull") if s.name == "upload"}
    finally:
        srv.stop()
    assert int(out["0"].num_rows) > 0 and int(out["1"].num_rows) == 0
    assert all_on_device(out["0"]) and all_on_device(out["1"])
    assert serde._EXCHANGE_PAGE.value(side="fuse", form="host") \
        > fused_before
    # only what was pulled is fused; the empty page is made where it is
    assert set(spans) == {"0"}
    assert spans["0"]["device_fetches"] == 0
    assert spans["0"]["device_puts"] == len(
        jax.tree_util.tree_leaves(out["0"]))
    assert spans["0"]["bytes"] == column_mod.page_nbytes(out["0"])


def test_streamed_chunks_are_device_pages(monkeypatch):
    """`_run_streaming_remote`: every chunk a row-preserving stage runs
    is the fuse's device page."""
    sf = 0.001
    recorded = _RecordedInputs(monkeypatch)
    streamed = []
    inner = TpuTaskManager._run_streaming_remote

    def run_streaming_remote(tm, task, plan, ex):
        took = inner(tm, task, plan, ex)
        streamed.append(took)
        return took

    monkeypatch.setattr(TpuTaskManager, "_run_streaming_remote",
                        run_streaming_remote)
    srv = TpuWorkerServer(TpchConnector(sf)).start()
    try:
        _post(srv.port, "s1.0.0.0", task_update_request(
            _project_fragment(sf), n_splits=3, sf=sf))
        rev = var("revenue", "double")
        remote = S.RemoteSourceNode(
            id="0", sourceFragmentIds=["0"], outputVariables=[rev])
        from tests.protocol_fixtures import call
        keep = call("GREATER_THAN_OR_EQUAL",
                    "$operator$greater_than_or_equal", "boolean",
                    [rev, rev], ["double", "double"])
        tur2 = task_update_request(
            fragment("1", S.FilterNode(id="1", source=remote,
                                       predicate=keep), [rev], ["0"]),
            n_splits=0, sf=sf)
        tur2.sources = [S.TaskSource(
            planNodeId="0",
            splits=[S.ScheduledSplit(
                sequenceId=0, planNodeId="0",
                split=S.Split(connectorId="$remote", connectorSplit={
                    "location":
                        f"http://127.0.0.1:{srv.port}/v1/task/s1.0.0.0",
                    "bufferId": "0"}))],
            noMoreSplits=True)]
        _post(srv.port, "s2.0.0.0", tur2)
        frames = PageStream(
            f"http://127.0.0.1:{srv.port}/v1/task/s2.0.0.0").drain()
    finally:
        srv.stop()
    rows = sum(int(p.num_rows) for p in decode_pages(frames, [DOUBLE]))
    assert rows > 0
    assert streamed == [True]
    assert recorded.pages and all(all_on_device(p) for p in recorded.pages)
    assert sum(int(p.num_rows) for p in recorded.pages) == rows


# -- (v) the served path -----------------------------------------------------

def test_q3_served_crosses_the_exchange_on_the_host(monkeypatch):
    """Two workers, SF0.01, `POST /v1/statement`: every partition, every
    decoded page and every page into a fuse is a host page; the spans
    around the work count no array between host and device but the fused
    pages' own; every remote input of every task is a device page."""
    recorded = _RecordedInputs(monkeypatch)
    connector = TpchConnector(0.01)
    query = qgen.load_query("q03")
    params = {"SEGMENT": "BUILDING", "DATE": "1995-03-15"}
    want = compare.load_reference(query)(bench_run.Tables(connector), params)
    sides = ("partition", "decode", "fuse")
    before = {(s, f): serde._EXCHANGE_PAGE.value(side=s, form=f)
              for s in sides for f in ("host", "device")}
    # partitioned joins, as at SF1: the probe is hash-exchanged
    cluster = TpuCluster(connector, n_workers=2, session_properties={
        "broadcast_join_threshold_rows": 100})
    server = StatementServer(cluster).start()
    try:
        _columns, rows = run_statement(server.base,
                                       query["sql"].format(**params))
        spans = TRACER.get(cluster.last_trace_id)
    finally:
        server.stop()
        cluster.stop()
    gaps = compare.row_gaps([list(r) for r in rows], want)
    assert gaps["wrong_cells"] == 0 and gaps["max_rel_err"] <= 1e-9
    counted = {k: serde._EXCHANGE_PAGE.value(side=k[0], form=k[1]) - v
               for k, v in before.items()}
    for side in sides:
        assert counted[side, "host"] > 0, counted
        assert counted[side, "device"] == 0, counted
    serialize = [s.attributes for s in spans if s.name == "serialize"]
    deserialize = [s.attributes for s in spans if s.name == "deserialize"]
    fuses = [s.attributes for s in spans
             if s.name == "upload" and "device_fetches" in s.attributes]
    assert serialize and deserialize and fuses
    assert any(a["buffers"] > 1 for a in serialize)
    assert [a["device_puts"] for a in serialize] == [0] * len(serialize)
    assert [a["device_fetches"] for a in deserialize] == \
        [0] * len(deserialize)
    assert [a["device_fetches"] for a in fuses] == [0] * len(fuses)
    assert all(a["device_puts"] > 0 and a["bytes"] > 0 for a in fuses)
    # a scan's upload is not a fuse: it carries a table, not a source
    assert all("source" in a and "table" not in a for a in fuses)
    assert recorded.pages and all(all_on_device(p) for p in recorded.pages)
