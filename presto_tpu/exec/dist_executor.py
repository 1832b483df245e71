"""DistExecutor — the whole SQL plan as ONE shard_map program over a mesh.

Reference roles, fused into a single compiled unit:
  - AddExchanges/PlanFragmenter decide the distribution (plan/fragment.py)
  - each fragment's operator pipeline = the same local operator lowering
    the single-chip Executor uses (inherited)
  - every ExchangeNode lowers to an ICI collective: hash repartition ->
    lax.all_to_all, broadcast -> all_gather, single -> all_gather + only
    device 0 keeps rows (the coordinator-facing SINGLE distribution,
    reference SystemPartitioningHandle.SINGLE)

The reference runs fragments as separate tasks streaming pages over HTTP
(SqlStageExecution / ExchangeClient.java:71); on one multi-chip TPU worker
the fragments are instead fused into one XLA program so the compiler
overlaps compute with the collectives — the exchanges become program edges,
not network calls. Across hosts the same fragment tree maps onto the HTTP
pull protocol (protocol/, server/).

Overflow-retry: per-node counters (group counts, join duplicates, exchange
receive totals and per-peer send maxima) are maxed over the mesh and
fetched in one host sync; the generic retry loop re-lowers at bigger
buckets, exactly like the local executor.
"""

from __future__ import annotations

from typing import Callable, List

import jax
import jax.numpy as jnp

from presto_tpu.data.column import Page, bucket_capacity
from presto_tpu.exec.executor import Executor, ScanSpec
from presto_tpu.obs.metrics import counter as _metric_counter
from presto_tpu.parallel.mesh import AXIS, run_sharded, stack_pages, \
    unstack_page
from presto_tpu.parallel.shuffle import ExchangeLayout, all_gather_page, \
    mesh_max, partition_ids, repartition_page
from presto_tpu.plan.fragment import add_exchanges
from presto_tpu.plan.nodes import Partitioning, PlanNode, Step

#: ICI exchange observability (the mesh analog of the HTTP "Exchange:"
#: counters): static wire-buffer bytes and collective launches per
#: exchange kind, exchange-driven overflow re-lowers, and distinct
#: fragment programs compiled. All feed /v1/metrics and the "Mesh:"
#: line in EXPLAIN ANALYZE.
_M_MESH_BYTES = _metric_counter(
    "presto_tpu_mesh_exchange_bytes_total",
    "Static wire-buffer bytes moved by packed ICI collectives",
    ("kind",))
_M_MESH_LAUNCHES = _metric_counter(
    "presto_tpu_mesh_collective_launches_total",
    "Packed ICI collectives launched (one per distinct lane dtype)",
    ("kind",))
_M_MESH_OVERFLOW = _metric_counter(
    "presto_tpu_mesh_exchange_overflow_retries_total",
    "Exchange re-lowers forced by per-peer chunk or receive-capacity "
    "overflow")
_M_MESH_COMPILES = _metric_counter(
    "presto_tpu_mesh_fragment_compiles_total",
    "Distinct fragment programs compiled by the mesh executor")


class DistExecutor(Executor):
    """Executes plans distributed over an N-device mesh (CPU mesh in
    tests, TPU ICI in production)."""

    # the whole distributed plan lowers into ONE shard_map program
    # (exchanges are ICI collectives inside it) — island splitting does
    # not apply here
    _force_fused = True

    def __init__(self, connector, mesh, session=None, history=None):
        super().__init__(connector, session=session)
        self.mesh = mesh
        self.ndev = int(mesh.devices.size)
        # HBO store consulted by add_exchanges at _prepare time
        self.history = history
        # id(exchange node) -> ExchangeLayout, recorded at trace time by
        # the packed collectives; _trace_credit marks exchanges whose
        # first dispatch still owes its metric increment to the trace.
        self._exchange_layout = {}
        self._trace_credit = set()
        # per-query mesh counters behind the EXPLAIN ANALYZE "Mesh:" line
        self.last_mesh_stats = None

    # ---- fragment-by-fragment execution ---------------------------------
    # One XLA program per fragment (not one giant fused program): compile
    # sizes stay bounded — mirroring the reference's per-stage tasks —
    # and every cut exchange becomes a consumer-side collective over the
    # producer fragment's materialized sharded page (the pull model).
    def execute(self, plan: PlanNode) -> Page:
        import time
        budget = self.session["query_max_execution_time"]
        self._deadline = (time.time() + budget) if budget else None
        self.last_node_rows = {}
        self._node_map = {}
        plan = self._resolve_subqueries(plan)
        plan = self._prepare(plan)
        return self._execute_prepared(plan)

    def _execute_prepared(self, plan: PlanNode) -> Page:
        from presto_tpu.plan.fragment import create_fragments
        frags = create_fragments(plan)
        by_id = {f.fragment_id: f for f in frags}
        self.last_mesh_stats = {
            "ndev": self.ndev, "fragments": len(frags),
            "collectives": 0, "wire_bytes": 0,
            "overflow_retries": 0, "fragment_compiles": 0}
        # donation analog for the repartition scratch: a fragment result
        # is freed as soon as its last consumer converged (the retry
        # loop re-reads inputs, so true jit donation is unsafe — but a
        # converged consumer never re-reads its upstream)
        refs = {}
        for f in frags:
            for c in set(f.remote_sources):
                refs[c] = refs.get(c, 0) + 1
        self._frag_results = {}
        done = set()

        def run(fid: int):
            if fid in done:
                return
            for c in by_id[fid].remote_sources:
                run(c)
            # stats ids must not collide across fragments: give each
            # fragment its own id space (the island-mode mechanism)
            self._stats_base = (fid + 1) << 20
            self._frag_results[fid] = self._execute_tree(by_id[fid].root)
            done.add(fid)
            for c in set(by_id[fid].remote_sources):
                refs[c] -= 1
                if refs[c] == 0 and c != 0:
                    self._free_page(self._frag_results.pop(c))

        try:
            run(0)
            return self._frag_results[0]
        finally:
            self._frag_results = {}
            self._stats_base = 0

    @staticmethod
    def _free_page(page: Page) -> None:
        """Release a dead fragment result's device buffers eagerly
        instead of waiting for GC (jit outputs — never aliased with
        connector-cached scan pages, so deletion cannot corrupt them)."""
        for leaf in jax.tree_util.tree_leaves(page):
            delete = getattr(leaf, "delete", None)
            if delete is not None:
                try:
                    delete()
                except Exception:   # noqa: BLE001 — freeing is advisory
                    pass

    def _remote_input(self, node, scans):
        from presto_tpu.exec.executor import RemoteSpec
        page = self._frag_results[node.remote_fragment]
        idx = len(scans)
        scans.append(RemoteSpec(node.remote_fragment, page.capacity))
        return (lambda pages: pages[idx]), page.capacity

    # ---- hook overrides -------------------------------------------------
    # Every device-mesh hook delegates to the single-device base path
    # when ndev == 1: a 1-device "mesh" still executes FRAGMENT-WISE
    # (one program per fragment, the mode bench uses for join-heavy
    # queries) but needs no shard_map or collectives. With ndev > 1 the
    # int64 counters are reduced by parallel/shuffle.mesh_max: the TPU
    # compiler lowers only Sum all-reduces for 64-bit integers (x64 is
    # on, presto_tpu/__init__.py), so a lax.pmax there does not compile.
    def _prepare(self, plan: PlanNode) -> PlanNode:
        return add_exchanges(plan, self.connector, self.session,
                             self.history)

    def _wrap(self, fn: Callable) -> Callable:
        if self.ndev == 1:
            return super()._wrap(fn)

        def wrapped(pages, params=()):
            # the lifted literals are the same on every device: the
            # local function closes over them
            def local_fn(*locals_):
                out, counters = fn(list(locals_), params)
                if counters.shape[0]:
                    counters = mesh_max(counters)
                return out, counters
            return run_sharded(self.mesh, local_fn, *pages,
                               with_needed=True)
        return wrapped

    def _page_rows(self, page: Page) -> List[tuple]:
        if self.ndev == 1:
            return super()._page_rows(page)
        rows: List[tuple] = []
        for p in unstack_page(page):
            rows.extend(p.to_pylist())
        return rows

    def _scan_rows(self, node) -> int:
        if self.ndev == 1:
            return super()._scan_rows(node)
        t = self.connector.table(node.table)
        per = (t.num_rows + self.ndev - 1) // self.ndev
        return max(per, 1)

    def _fetch(self, s) -> Page:
        from presto_tpu.exec.executor import RemoteSpec
        if isinstance(s, RemoteSpec):
            return self._frag_results[s.fragment_id]
        return super()._fetch(s)

    def _scan_page(self, s) -> Page:
        if self.ndev == 1:
            return super()._scan_page(s)
        pages = [self.connector.table(s.table, part=d,
                                      num_parts=self.ndev)
                 .page(columns=list(s.columns), capacity=s.capacity)
                 for d in range(self.ndev)]
        return stack_pages(pages)

    def _unique_ids(self, p: Page) -> jnp.ndarray:
        if self.ndev == 1:
            return super()._unique_ids(p)
        d = jax.lax.axis_index(AXIS).astype(jnp.int64)
        return d * p.capacity + jnp.arange(p.capacity, dtype=jnp.int64)

    def _finish_values(self, out: Page) -> Page:
        if self.ndev == 1:
            return super()._finish_values(out)
        # VALUES is a single stream: device 0 emits, the rest are empty
        # (the fragmenter marks it SINGLE-partitioned).
        on0 = jnp.where(jax.lax.axis_index(AXIS) == 0, out.num_rows, 0)
        return Page(out.columns, on0.astype(jnp.int32), out.names)

    def _finish_agg(self, node, out: Page) -> Page:
        if self.ndev == 1:
            return super()._finish_agg(node, out)
        if node.group_fields or node.step == Step.PARTIAL:
            return out
        # Global FINAL aggregation after a SINGLE exchange: every device
        # ran the (empty-input-tolerant) one-row aggregation, but only
        # device 0 received rows — only its row is the answer.
        on0 = jnp.where(jax.lax.axis_index(AXIS) == 0, out.num_rows, 0)
        return Page(out.columns, on0.astype(jnp.int32), out.names)

    # ---- mesh observability --------------------------------------------
    def _mesh_sink(self, node, kind: str):
        """Per-dispatch exchange accounting. The packed layout (launch
        count, wire bytes) is only known at trace time; once recorded it
        is charged host-side on every later dispatch, and the first
        dispatch's charge is deferred to its own trace (`_trace_credit`)
        so retraces after capacity growth never double-count."""
        key = id(node)

        def sink(layout, key=key, kind=kind):
            self._exchange_layout[key] = ExchangeLayout(
                kind, layout.collectives, layout.wire_bytes)
            if key in self._trace_credit:
                self._trace_credit.discard(key)
                self._account_exchange(key)
        if key in self._exchange_layout:
            self._account_exchange(key)
        else:
            self._trace_credit.add(key)
        return sink

    def _account_exchange(self, key) -> None:
        lay = self._exchange_layout[key]
        _M_MESH_LAUNCHES.inc(lay.collectives, kind=lay.kind)
        _M_MESH_BYTES.inc(lay.wire_bytes, kind=lay.kind)
        st = self.last_mesh_stats
        if st is not None:
            st["collectives"] += lay.collectives
            st["wire_bytes"] += lay.wire_bytes

    def _grow_caps(self, pending, needed) -> bool:
        if self.ndev > 1:
            caps = pending["caps"]
            if any(isinstance(k, tuple) and int(n) > caps[k]
                   for k, n in zip(pending["watch"], needed)):
                _M_MESH_OVERFLOW.inc()
                if self.last_mesh_stats is not None:
                    self.last_mesh_stats["overflow_retries"] += 1
        return super()._grow_caps(pending, needed)

    def _note_compile(self, plan: PlanNode) -> None:
        _M_MESH_COMPILES.inc()
        if self.last_mesh_stats is not None:
            self.last_mesh_stats["fragment_compiles"] += 1

    def _lower_exchange(self, node, nid, src, cap, caps, watch, _needed):
        if self.ndev == 1:
            # exchanges between fragments are identity relabels on one
            # device; the fragment-wise materialization still happens
            return super()._lower_exchange(node, nid, src, cap, caps,
                                           watch, _needed)
        ndev = self.ndev
        if node.partitioning in (Partitioning.HASH, Partitioning.RANGE):
            from presto_tpu.parallel.shuffle import range_partition_ids
            if node.partitioning == Partitioning.HASH:
                pid_fn = lambda p: partition_ids(p, node.keys, ndev)  # noqa: E731
                kind = "hash"
            else:
                pid_fn = lambda p: range_partition_ids(  # noqa: E731
                    p, node.sort_keys[0], ndev)
                kind = "range"
            out_cap = caps.get((nid, "cap")) or bucket_capacity(2 * cap)
            factor = self.session["exchange_chunk_factor"]
            chunk = caps.get((nid, "chunk")) \
                or max(factor * cap // ndev, 64)
            caps[(nid, "cap")] = out_cap
            caps[(nid, "chunk")] = chunk
            watch.append((nid, "cap"))
            watch.append((nid, "chunk"))
            sink = self._mesh_sink(node, kind)

            def repart_fn(pages, node=node, out_cap=out_cap, chunk=chunk,
                          sink=sink):
                p = src(pages)
                out, total, max_send = repartition_page(
                    p, pid_fn(p), ndev, out_cap, chunk,
                    layout_sink=sink)
                _needed.append(total)
                _needed.append(max_send)
                return Page(out.columns, out.num_rows, node.output_names)
            return repart_fn, out_cap

        if node.partitioning == Partitioning.BROADCAST:
            sink = self._mesh_sink(node, "broadcast")

            def bcast_fn(pages, node=node, sink=sink):
                p = src(pages)
                out = all_gather_page(p, ndev, layout_sink=sink)
                return Page(out.columns, out.num_rows, node.output_names)
            return bcast_fn, ndev * cap

        if node.partitioning == Partitioning.SINGLE:
            sink = self._mesh_sink(node, "single")

            def single_fn(pages, node=node, sink=sink):
                p = src(pages)
                out = all_gather_page(p, ndev, layout_sink=sink)
                on0 = jnp.where(jax.lax.axis_index(AXIS) == 0,
                                out.num_rows, 0)
                return Page(out.columns, on0.astype(jnp.int32),
                            node.output_names)
            return single_fn, ndev * cap

        raise NotImplementedError(f"exchange {node.partitioning}")


class DistSplitExecutor(DistExecutor):
    """Mesh executor with lifespan splits: the batched driver assigns one
    (part, num_parts) split of the driving table per lifespan; each mesh
    device then reads sub-split `part*ndev + d` of `num_parts*ndev`, so a
    lifespan's working set stays bounded PER DEVICE. This is the
    composition of exec/lifespan.BatchedRunner's driving-scan streaming
    with the distributed exchange lowering (grouped execution over
    lifespans, run on the mesh)."""

    def __init__(self, connector, mesh, session=None, history=None):
        super().__init__(connector, mesh, session=session,
                         history=history)
        self.splits = {}

    def set_splits(self, by_table) -> None:
        self.splits = by_table

    def _split_tables(self, name):
        parts = self.splits.get(name)
        if parts is None:
            return None
        # each assigned split (b, n) subdivides across the mesh: device
        # d reads part b*ndev+d of n*ndev. A task holding SEVERAL
        # lifespan splits (the fused cluster-mesh plan concentrates a
        # whole stage's splits on one task) gives each device one
        # subpart per split, merged at fetch time.
        out = []
        for d in range(self.ndev):
            ts = [self.connector.table(name, part=b * self.ndev + d,
                                       num_parts=n * self.ndev)
                  for b, n in parts]
            out.append(ts[0] if len(ts) == 1 else _MultiPartTable(ts))
        return out

    def _scan_rows(self, node) -> int:
        ts = self._split_tables(node.table)
        if ts is None:
            return super()._scan_rows(node)
        return max(max(t.num_rows for t in ts), 1)

    def _scan_page(self, s) -> Page:
        ts = self._split_tables(s.table)
        if ts is None:
            return super()._scan_page(s)
        pages = [t.page(columns=list(s.columns), capacity=s.capacity)
                 for t in ts]
        return pages[0] if self.ndev == 1 else stack_pages(pages)


class _MultiPartTable:
    """Several connector part-tables presented as one: a device's view
    of a task that holds multiple lifespan splits of one table."""

    def __init__(self, tables):
        self.tables = tables
        self.num_rows = sum(t.num_rows for t in tables)

    def page(self, columns=None, capacity=None):
        from presto_tpu.data.column import concat_pages_host
        pages = [t.page(columns=columns) for t in self.tables]
        return concat_pages_host(pages, capacity=capacity)


class DistEngine:
    """Parse -> plan -> distributed execute over a mesh. Reference role:
    DistributedQueryRunner (presto-tests/.../DistributedQueryRunner.java:114)
    — N workers in one process, real exchanges between them."""

    def __init__(self, connector, mesh, session=None, history=None):
        from presto_tpu.sql.analyzer import Planner

        self.connector = connector
        self.planner = Planner(connector)
        self.executor = DistExecutor(connector, mesh, session=session,
                                     history=history)
        self._plans = {}

    def plan_sql(self, sql: str) -> PlanNode:
        if sql not in self._plans:
            from presto_tpu.sql.parser import parse_sql
            self._plans[sql] = self.planner.plan_query(parse_sql(sql))
        return self._plans[sql]

    def explain_sql(self, sql: str) -> str:
        from presto_tpu.plan.nodes import explain
        return explain(self.plan_sql(sql))

    def explain_analyze_sql(self, sql: str) -> str:
        from presto_tpu.exec.stats import explain_analyze
        return explain_analyze(self, sql)

    @property
    def session(self):
        return self.executor.session

    def execute_sql(self, sql: str) -> List[tuple]:
        head = sql.lstrip().split(None, 1)[0].lower() if sql.strip() \
            else ""
        if head == "explain":
            # EXPLAIN [ANALYZE] over the distributed plan — the mesh
            # analog of LocalEngine's dispatch
            rest = sql.lstrip()[len("explain"):].lstrip()
            if rest.lower().startswith("analyze"):
                text = self.explain_analyze_sql(
                    rest[len("analyze"):].lstrip())
            else:
                text = self.explain_sql(rest)
            return [(line,) for line in text.splitlines()]
        stacked = self.executor.execute(self.plan_sql(sql))
        rows = self.executor._page_rows(stacked)
        self._record_history()
        return rows

    def _record_history(self):
        """Feed observed per-node rows into the HBO store after execution
        (mirrors LocalEngine._record_history; requires collect_stats)."""
        ex = self.executor
        if ex.history is None or not getattr(ex, "last_node_rows", None):
            return
        from presto_tpu.plan.stats import canonical_key
        for nid, rows_n in ex.last_node_rows.items():
            entry = ex._node_map.get(nid)
            if entry is not None:
                ex.history.record(canonical_key(entry[0]), rows_n)
