"""Lifespan-batched execution: bounded working sets for big scans.

Reference roles: grouped execution over bucket lifespans
(presto-main-base/.../execution/Lifespan.java,
sql/planner/GroupedExecutionTagger.java) and the split-streaming driver
loop (SqlTaskExecution.java:509): instead of materializing the whole
driving table, stream K row-range lifespans of it through the compiled
fragment, accumulating PARTIAL aggregation states, and finish with one
FINAL aggregation over the concatenated partials. Memory is bounded by
the per-lifespan capacity — the executor's static accounting
(MemoryLimitExceeded) decides when batching is needed.

Applies to plans whose root path is
Output -> [Sort|TopN|Limit]* -> Aggregation(single) -> <pipeline over the
driving scan> — the shape of every aggregation-rooted TPC-H query.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from presto_tpu.data.column import Column, Page, bucket_capacity
from presto_tpu.exec.executor import MemoryLimitExceeded
from presto_tpu.exec.split_executor import SplitExecutor
from presto_tpu.ops.aggregate import grouped_aggregate
from presto_tpu.ops.sort import limit_page, sort_page, top_n
from presto_tpu.plan.nodes import (
    AggregationNode, FilterNode, LimitNode, OutputNode, PlanNode,
    ProjectNode, SortNode, Step, TableScanNode, TopNNode,
)


def _root_chain(plan: PlanNode):
    """(above_chain, agg) where above_chain are the row-wise/ordering
    nodes over the root aggregation (Output, Sort, TopN, Limit, the final
    projection, HAVING filters); None if the plan has no such shape."""
    above: List[PlanNode] = []
    node = plan
    while isinstance(node, (OutputNode, SortNode, TopNNode, LimitNode,
                            ProjectNode, FilterNode)):
        above.append(node)
        node = node.source
    if isinstance(node, AggregationNode) and node.step == Step.SINGLE:
        return above, node
    return None


def _driving_scan(connector, plan: PlanNode) -> Optional[str]:
    """The largest table scanned — the one worth streaming."""
    best, best_rows = None, -1

    def walk(n):
        nonlocal best, best_rows
        if isinstance(n, TableScanNode):
            rows = connector.table(n.table).num_rows
            if rows > best_rows:
                best, best_rows = n.table, rows
        for c in n.children():
            if c is not None:
                walk(c)
    walk(plan)
    return best


def _streamable(below_agg: PlanNode, driving: str) -> bool:
    """True iff every occurrence of the driving scan reaches the root
    aggregation only through row-preserving paths: filters, projections
    and the PROBE side of inner/left joins. A driving scan under a nested
    aggregation, a join build/filtering side, a window or a sort would
    make per-batch partials non-additive — batching would silently
    corrupt results, so those shapes fall back to single-shot."""
    return _streamable_from(
        below_agg,
        lambda n: isinstance(n, TableScanNode) and n.table == driving)


def _streamable_from(below_agg: PlanNode, is_driving) -> bool:
    """Generalized additivity check: `is_driving(node)` marks the
    streamed input (a table scan lifespan, or a RemoteSourceNode whose
    pages arrive in chunks — server/task_manager's non-leaf streaming)."""
    from presto_tpu.plan.nodes import JoinNode, JoinType, RemoteSourceNode

    def has_driving(n) -> bool:
        if is_driving(n):
            return True
        return any(c is not None and has_driving(c)
                   for c in n.children())

    def ok(n) -> bool:
        if is_driving(n):
            return True
        if isinstance(n, (TableScanNode, RemoteSourceNode)):
            return True
        if isinstance(n, (FilterNode, ProjectNode)):
            return ok(n.source)
        if isinstance(n, JoinNode):
            if has_driving(n.build):
                return False
            if n.join_type not in (JoinType.INNER, JoinType.LEFT,
                                   JoinType.SEMI, JoinType.ANTI,
                                   JoinType.ANTI_EXISTS):
                return False
            return ok(n.probe)
        # Any other node (nested aggregation, window, sort, unique-id)
        # between the driving input and the root agg is non-streamable.
        return not has_driving(n)

    return ok(below_agg)


def _dynamic_filter(connector, ex: SplitExecutor, agg_source: PlanNode,
                    driving: str):
    """Build-side dynamic filter (reference: DynamicFilterSourceOperator +
    LocalDynamicFilter feeding probe-side scans). TPU-shaped realization:
    the compiled fragment's shapes are static, so the win is HOST-side —
    execute the topmost non-driving build subtree once, take its join-key
    [min, max], and skip whole lifespans whose driving-scan key slice
    cannot intersect. Returns (scan column name, lo, hi, build_empty) or
    None when no eligible join exists."""
    from presto_tpu.expr.nodes import InputRef
    from presto_tpu.plan.nodes import JoinNode, JoinType

    def scans_driving(n) -> bool:
        if isinstance(n, TableScanNode):
            return n.table == driving
        return any(c is not None and scans_driving(c)
                   for c in n.children())

    def scan_column(n, channel: int):
        """Resolve `channel` of n's output to a raw driving-scan column
        name through Filter/Project/probe-side-join chains."""
        if isinstance(n, TableScanNode):
            return n.columns[channel] if n.table == driving else None
        if isinstance(n, FilterNode):
            return scan_column(n.source, channel)
        if isinstance(n, ProjectNode):
            e = n.expressions[channel]
            if isinstance(e, InputRef):
                return scan_column(n.source, e.field)
            return None
        if isinstance(n, JoinNode):
            if channel < len(n.probe.output_types):
                return scan_column(n.probe, channel)
            return None
        return None

    def find(n):
        if isinstance(n, JoinNode) \
                and n.join_type in (JoinType.INNER, JoinType.SEMI) \
                and len(n.probe_keys) >= 1 \
                and not scans_driving(n.build):
            col = scan_column(n.probe, n.probe_keys[0])
            if col is not None:
                return n, col
        for c in n.children():
            if c is not None and scans_driving(c):
                r = find(c)
                if r is not None:
                    return r
        return None

    hit = find(agg_source)
    if hit is None:
        return None
    join, col = hit
    # string keys: dictionary codes are only comparable for aligned
    # dictionaries; restrict the filter to numeric/date keys
    if join.build.output_types[join.build_keys[0]].is_string:
        return None
    build_page = ex.execute(join.build)
    if getattr(ex, "ndev", 1) > 1:
        from presto_tpu.parallel.mesh import unstack_page
        pages = unstack_page(build_page)
    else:
        pages = [build_page]
    parts = []
    for p in pages:
        key = p.columns[join.build_keys[0]]
        n = int(p.num_rows)
        if n:
            v = np.asarray(key.values)[:n][~np.asarray(key.nulls)[:n]]
            if len(v):
                parts.append(v)
    if not parts:
        return (col, 0, -1, True)
    v = np.concatenate(parts)
    return (col, v.min(), v.max(), False)


@dataclasses.dataclass
class _HostPartial:
    """A spilled partial: plain numpy, no device residency. The TPU spill
    analog (reference: spiller/FileSingleStreamSpiller +
    MemoryRevokingScheduler): HBM holds only the in-flight lifespan;
    accumulated partials live in host RAM until the final merge."""
    columns: List[tuple]       # (values np, nulls np, Type, StringDict)
    num_rows: int
    names: tuple


def _dec128_host(c, n: int):
    """Exact host image of a Decimal128Column's limb lanes (the float
    image to_numpy produces loses exactness past 2^53 — the round-4
    `_HostPartial` hole). Marker tuple:
    ("dec128", (l3, l2, l1, l0), count|None)."""
    lanes, nl, cnt = c._host()
    return (("dec128", tuple(np.array(x[:n]) for x in lanes),
             None if cnt is None else np.array(cnt[:n])),
            np.array(nl[:n]), c.type, None)


def _spill_to_host(p: Page) -> _HostPartial:
    from presto_tpu.data.column import Decimal128Column
    n = int(p.num_rows)
    cols = []
    for c in p.columns:
        if isinstance(c, Decimal128Column):
            cols.append(_dec128_host(c, n))
            continue
        v, nl = c.to_numpy(n)
        cols.append((np.array(v), np.array(nl), c.type, c.dictionary))
    return _HostPartial(cols, n, p.names)


def _part_cols(p, spiller=None):
    from presto_tpu.data.column import Decimal128Column
    from presto_tpu.exec.spill import SpillHandle
    if isinstance(p, SpillHandle):
        p = spiller.read(p)            # disk -> device page
    if isinstance(p, _HostPartial):
        return p.columns
    n = int(p.num_rows)
    return [(_dec128_host(c, n) if isinstance(c, Decimal128Column)
             else (np.asarray(c.values)[:n], np.asarray(c.nulls)[:n],
                   c.type, c.dictionary)) for c in p.columns]


def _concat_pages(pages: List, spiller=None) -> Page:
    """Host-side concatenation of the valid rows of several partials
    (device Pages, host-RAM _HostPartials, or disk SpillHandles) with
    identical schemas. Decimal128 limb lanes concatenate exactly."""
    from presto_tpu.data.column import Decimal128Column
    parts = [_part_cols(p, spiller) for p in pages]
    total = sum(int(p.num_rows) for p in pages)
    cap = bucket_capacity(max(total, 1))
    cols = []
    for i, (v0, _n0, t0, d0) in enumerate(parts[0]):
        nulls = np.concatenate([pc[i][1] for pc in parts])
        if isinstance(v0, tuple) and v0 and v0[0] == "dec128":
            def lane(j):
                a = np.concatenate([pc[i][0][1][j] for pc in parts])
                out = np.zeros(cap, dtype=np.int64)
                out[:total] = a
                return jnp.asarray(out)
            cnts = [pc[i][0][2] for pc in parts]
            count = None
            if cnts[0] is not None:
                ca = np.concatenate(cnts)
                cout = np.zeros(cap, dtype=np.int64)
                cout[:total] = ca
                count = jnp.asarray(cout)
            nl = np.ones(cap, dtype=bool)
            nl[:total] = nulls
            cols.append(Decimal128Column(
                lane(0), lane(1), lane(2), lane(3),
                jnp.asarray(nl), t0, count))
            continue
        vals = np.concatenate([pc[i][0] for pc in parts])
        cols.append(Column.from_numpy(vals, t0, nulls=nulls,
                                      dictionary=d0, capacity=cap))
    return Page.from_columns(cols, total, pages[0].names)


class BatchedRunner:
    """Prepared lifespan-batched execution: plan analysis, partial-plan
    construction and the SplitExecutor (with its compiled-program memo)
    are built ONCE; run() executes all lifespans and the final merge.
    Repeat run() calls reuse the jitted programs — the shape the bench
    needs for warm timing, and the worker for repeated tasks."""

    def __init__(self, connector, plan: PlanNode, num_batches: int,
                 memory_limit_bytes: Optional[int] = None, session=None,
                 mesh=None):
        from presto_tpu.plan.fragment import (
            _UNSPLITTABLE, _partial_agg_layout,
        )

        self.connector = connector
        self.num_batches = num_batches
        resolver = SplitExecutor(connector)
        plan = resolver._resolve_subqueries(plan)
        self.plan = plan
        chain = _root_chain(plan)
        driving = _driving_scan(connector, plan)
        self.batchable = not (
            chain is None or driving is None or num_batches <= 1
            or not _streamable(chain[1].source, driving)
            # sketch aggregates have no column-shaped partial state —
            # same rule as the fragmenter's reshard-instead-of-split
            or any(a.kind in _UNSPLITTABLE for a in chain[1].aggs))
        if mesh is not None:
            # distributed lifespan batching: each lifespan's partial
            # runs on the device mesh, sub-split per device
            from presto_tpu.exec.dist_executor import DistSplitExecutor
            self.ex = DistSplitExecutor(connector, mesh, session=session)
        else:
            self.ex = SplitExecutor(connector, session=session)
        self.ex.memory_limit_bytes = memory_limit_bytes
        self.driving = driving
        if not self.batchable:
            return
        self.above, self.agg = chain
        partial_specs, final_specs, pnames, ptypes = \
            _partial_agg_layout(self.agg)
        self.final_specs = final_specs
        self.partial_plan = AggregationNode(
            pnames, ptypes, source=self.agg.source,
            group_fields=self.agg.group_fields, aggs=tuple(partial_specs),
            step=Step.PARTIAL, group_count_hint=self.agg.group_count_hint)
        self.dyn = None
        if self.ex.session["dynamic_filtering_enabled"]:
            self.dyn = _dynamic_filter(connector, self.ex,
                                       self.agg.source, driving)
        self.spill = bool(self.ex.session["spill_enabled"])
        # spill_path set -> partials revoke to DISK files
        # (FileSingleStreamSpiller role); empty -> host RAM offload
        self.spill_dir = self.ex.session["spill_path"] or None
        # streaming scans (the scale ladder): bound the rows one leaf
        # scan materializes, so a lifespan's working set is the run size,
        # not the split size. Mesh executors keep whole-split splits
        # (their sub-split sharding already bounds per-device rows).
        self.stream_rows = int(self.ex.session["streaming_scan_rows"] or 0)

    def _host_pages(self, p: Page) -> List[Page]:
        """A mesh executor returns a stacked sharded page — split it into
        per-device host pages; single-device pages pass through."""
        if getattr(self.ex, "ndev", 1) > 1:
            from presto_tpu.parallel.mesh import unstack_page
            return unstack_page(p)
        return [p]

    def run(self, stats: Optional[dict] = None) -> Page:
        if not self.batchable:
            out = self.ex.execute(self.plan)
            pages = self._host_pages(out)
            return pages[0] if len(pages) == 1 else _concat_pages(pages)
        connector, ex = self.connector, self.ex
        driving, num_batches = self.driving, self.num_batches
        spiller = None
        if self.spill and self.spill_dir:
            from presto_tpu.exec.spill import FileSpiller
            spiller = FileSpiller(self.spill_dir)
        try:
            merged = self._run_batches(stats, spiller)
        finally:
            # a query failing mid-spill must not leak run files (or, for
            # a spiller-owned tempdir, the directory itself)
            if spiller is not None:
                spiller.close()
        k = len(self.agg.group_fields)
        out_cap = bucket_capacity(max(int(merged.num_rows), 256))
        page, _groups = grouped_aggregate(merged, tuple(range(k)),
                                          tuple(self.final_specs),
                                          out_cap)
        page = Page(page.columns, page.num_rows, self.agg.output_names)
        return self._finish_above(page)

    def _run_batches(self, stats, spiller) -> Page:
        """Per-lifespan partial aggregation, spilled partials included;
        returns the concatenated partial page (spill files still live)."""
        connector, ex = self.connector, self.ex
        driving, num_batches = self.driving, self.num_batches
        skipped = 0
        partials: List[Page] = []
        for b in range(num_batches):
            if self.dyn is not None:
                col, lo, hi, empty = self.dyn
                t = connector.table(driving, part=b,
                                    num_parts=num_batches)
                if t.num_rows:
                    if empty:
                        skipped += 1
                        continue
                    # metadata min/max first (parquet row-group stats:
                    # prunes the lifespan WITHOUT reading the column);
                    # stats arrive normalized to engine representation,
                    # but a source that still yields raw logical values
                    # (dates/timestamps/varchar vs engine ints) must
                    # fall back to the column scan, never TypeError out
                    mm = (t.column_minmax(col)
                          if hasattr(t, "column_minmax") else None)
                    pruned = None
                    if mm is not None:
                        try:
                            pruned = bool(mm[0] > hi or mm[1] < lo)
                        except TypeError:
                            pruned = None
                    if pruned is None:
                        sv = t.arrays[col][:t.num_rows]
                        pruned = bool(sv.min() > hi or sv.max() < lo)
                    if pruned:
                        skipped += 1
                        continue
            for p in self._partial_pages(b):
                if self.spill:
                    if spiller is not None:
                        p = spiller.spill(p)
                    else:
                        p = _spill_to_host(p)
                partials.append(p)
        if stats is not None:
            stats.update(batches=num_batches, skipped=skipped)
        if not partials:
            # every lifespan pruned: run one anyway — pruned means its
            # join cannot match, so it yields the correct zero-state
            # partial (global aggregates still emit their count=0 row)
            ex.set_splits({driving: [(0, num_batches)]})
            partials.extend(
                self._host_pages(ex.execute(self.partial_plan)))

        if stats is not None and spiller is not None:
            stats.update(spilled_bytes=spiller.total_spilled_bytes,
                         spill_files=len(spiller.handles))
        return _concat_pages(partials, spiller)

    def _partial_pages(self, b: int):
        """Execute the partial plan over lifespan `b`, yielding its
        output pages. With streaming_scan_rows set (single-device
        executors only), the driving split flows through in bounded
        scan runs — connector.scan_runs — so the lifespan never holds
        its whole split resident; otherwise one whole-split shot."""
        ex = self.ex
        if (self.stream_rows > 0 and getattr(ex, "ndev", 1) == 1
                and hasattr(ex, "set_split_tables")
                and hasattr(self.connector, "scan_runs")):
            try:
                for run in self.connector.scan_runs(
                        self.driving, self.stream_rows, part=b,
                        num_parts=self.num_batches):
                    ex.set_split_tables({self.driving: run})
                    for p in self._host_pages(
                            ex.execute(self.partial_plan)):
                        yield p
            finally:
                ex.set_split_tables({})
            return
        ex.set_splits({self.driving: [(b, self.num_batches)]})
        for p in self._host_pages(ex.execute(self.partial_plan)):
            yield p

    def _finish_above(self, page: Page) -> Page:
        # Interpret the small chain above the aggregation.
        from presto_tpu.data.column import compact
        from presto_tpu.expr.params import evaluate

        for node in reversed(self.above):
            if isinstance(node, SortNode):
                page = sort_page(page, node.keys)
            elif isinstance(node, TopNNode):
                page = top_n(page, node.keys, node.count)
            elif isinstance(node, LimitNode):
                page = limit_page(page, node.count)
            elif isinstance(node, ProjectNode):
                cols = tuple(evaluate(e, page)
                             for e in node.expressions)
                page = Page(cols, page.num_rows, node.output_names)
            elif isinstance(node, FilterNode):         # HAVING
                c = evaluate(node.predicate, page)
                page = compact(page, ~c.nulls & c.values.astype(bool))
            else:  # OutputNode
                page = Page(page.columns, page.num_rows,
                            node.output_names)
        return page


def execute_batched(connector, plan: PlanNode, num_batches: int,
                    memory_limit_bytes: Optional[int] = None,
                    session=None, mesh=None,
                    stats: Optional[dict] = None) -> Page:
    """Execute `plan` streaming the driving scan in `num_batches`
    lifespans. Falls back to single-shot execution when the plan shape
    does not support batching (no root aggregation). With a `mesh`, each
    lifespan's partial runs distributed over the device mesh (sub-split
    per device). `stats` (if given) records {"batches", "skipped"} —
    dynamic-filter effectiveness."""
    return BatchedRunner(connector, plan, num_batches,
                         memory_limit_bytes, session, mesh=mesh).run(stats)


def execute_bounded(connector, plan: PlanNode,
                    memory_limit_bytes: int,
                    max_batches: int = 64,
                    session=None) -> Tuple[Page, int]:
    """Execute under a hard memory limit, doubling the lifespan count
    until the static plan footprint fits. Returns (page, batches_used).
    Reference role: the memory-pool + grouped-execution pairing that lets
    a bounded worker run arbitrarily large scans."""
    chain = _root_chain(plan)
    driving = _driving_scan(connector, plan)
    batchable = (chain is not None and driving is not None
                 and _streamable(chain[1].source, driving))
    batches = 1
    while True:
        try:
            return (execute_batched(connector, plan, batches,
                                    memory_limit_bytes,
                                    session=session), batches)
        except MemoryLimitExceeded:
            if not batchable or batches >= max_batches:
                raise
            batches *= 2
