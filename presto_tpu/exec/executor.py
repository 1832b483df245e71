"""Plan executor: lower a plan tree to ONE jit-compiled XLA program.

The reference executes a task as a pull-based chain of incremental operators
time-sliced on a thread pool (Driver.processFor,
presto-main-base/.../operator/Driver.java:310; TaskExecutor.java:87). That
model is wrong for XLA: here the *whole fragment* lowers to a single traced
function — scans arrive as device Pages, every operator is a pure
Page->Page transform, and XLA fuses across operator boundaries (the fusion
the reference gets piecemeal from PageProcessor codegen happens globally).

Dynamic cardinalities (join fan-out, group counts) use static capacity
buckets chosen from planner hints, with a host-side overflow-retry loop:
the compiled program also returns per-node "needed" counters; if any
exceeds its bucket, we re-lower at the next bucket and re-execute
(SURVEY.md §7.3 hard part #1 — the recompile is amortized across every
subsequent page/split batch at that bucket).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.data.column import (
    Column, Page, bucket_capacity, compact, page_nbytes,
)
from presto_tpu.expr.compile import binding, compile_expr
from presto_tpu.expr.params import lift_plan, same_objects
from presto_tpu.expr.nodes import (
    Call, InputRef, Literal, RowExpression, SpecialForm,
)
from presto_tpu.obs.metrics import (
    DEFAULT_ROWS_BUCKETS, DEFAULT_TIME_BUCKETS_S,
    histogram as _obs_histogram,
)
from presto_tpu.ops.aggregate import grouped_aggregate
from presto_tpu.ops.join import hash_join, merge_join
from presto_tpu.ops.sort import limit_page, sort_page, top_n
from presto_tpu.exec.program_cache import Program, ProgramCache
from presto_tpu.utils.tracing import TRACER, now
from presto_tpu.plan.nodes import (
    AggregationNode, AssignUniqueIdNode, ExchangeNode, FilterNode,
    GroupIdNode, JoinNode, JoinType, LimitNode, OutputNode, PlanNode,
    ProjectNode, RemoteSourceNode, SortNode, TableScanNode, TopNNode,
    MarkDistinctNode, TableWriterNode, UnionAllNode, UnnestNode,
    ValuesNode, WindowNode,
)

# per-operator execution histograms (OperatorStats role, scrapeable):
# wall seconds only exist on the profiled (collect_stats) island path —
# fused production dispatch deliberately has no per-operator sync —
# while output-row observations come from every converged program
_M_OP_WALL = _obs_histogram(
    "presto_tpu_operator_wall_seconds",
    "Per-operator island wall time (profiled executions)",
    ("operator",), buckets=DEFAULT_TIME_BUCKETS_S)
_M_OP_ROWS = _obs_histogram(
    "presto_tpu_operator_rows",
    "Per-operator output rows per execution", ("operator",),
    buckets=DEFAULT_ROWS_BUCKETS)


# What a lowering asks for as its inputs. Frozen: the specs are part of a
# program's cache key, capacities and all, because `_lower` closes Python
# integers derived from them into the program.
@dataclasses.dataclass(frozen=True)
class ScanSpec:
    table: str
    columns: Tuple[str, ...]
    capacity: int


@dataclasses.dataclass(frozen=True)
class RemoteSpec:
    """Input read from another fragment's result (the consumer side of a
    cut exchange; reference: RemoteSourceNode -> ExchangeOperator)."""
    fragment_id: int
    capacity: int


@dataclasses.dataclass(frozen=True)
class PageInputNode(PlanNode):
    """Placeholder leaf standing for an already-materialized child
    island's output page (island-split execution). Never appears in a
    coordinator plan — the executor synthesizes it when it cuts a plan
    into islands."""
    slot: int = 0


@dataclasses.dataclass(frozen=True)
class PageInputSpec:
    """Scan-slot marker resolved from the executor's per-execution
    island inputs (no connector fetch)."""
    slot: int
    capacity: int


class Overflow(Exception):
    def __init__(self, node_id: int, needed: int):
        self.node_id = node_id
        self.needed = needed


class QueryTimeoutError(RuntimeError):
    """query_max_execution_time exceeded (reference:
    QUERY_MAX_EXECUTION_TIME enforced by the QueryTracker). Checked at
    operator-island boundaries — a single compiled program is never
    interrupted mid-flight."""


class MemoryLimitExceeded(Exception):
    """Static plan footprint exceeds the executor's memory limit —
    the caller should batch (exec/lifespan.py) or reject the query.
    Reference role: MemoryPool reservation failure -> OOM kill
    (presto-main-base/.../memory/MemoryPool.java)."""

    def __init__(self, estimated: int, limit: int):
        super().__init__(
            f"plan needs ~{estimated // (1 << 20)} MiB device memory, "
            f"limit is {limit // (1 << 20)} MiB")
        self.estimated = estimated
        self.limit = limit


def _kind(node: PlanNode) -> str:
    """A plan node's operator name: its class less the `Node`."""
    return type(node).__name__.replace("Node", "")


def _operators(plan: PlanNode) -> List[str]:
    """The operator kinds inside one program, each once, root first."""
    kinds: Dict[str, None] = {}

    def walk(n):
        if n is not None:
            kinds.setdefault(_kind(n))
            for c in n.children():
                walk(c)
    walk(plan)
    return list(kinds)


def _row_bytes(types) -> int:
    """Bytes per row of a page with these column types (values + null
    mask lane) — the static footprint unit of capacity accounting."""
    return sum(t.dtype.itemsize + 1 for t in types)


class Executor:
    """Executes a plan against a connector. Compiles once per (plan,
    capacity assignment, input specs) in its program cache; overflow
    retries bump capacities."""

    def __init__(self, connector, session=None, programs=None):
        from presto_tpu.config import Session

        self.connector = connector
        self.session = session or Session()
        #: jitted programs and learned capacities: the worker's when a
        #: task manager built this executor, else its own
        self.programs: ProgramCache = (ProgramCache() if programs is None
                                       else programs)
        # Static memory accounting (reference: memory/MemoryPool.java —
        # here capacities are static, so the whole footprint is known at
        # lower time). None = unlimited.
        self.memory_limit_bytes = self.session["query_max_memory_per_node"]
        self.last_memory_estimate = 0
        #: how the last lowering joins, a JoinNode each in plan order:
        #: "merge" (ops/join.merge_join) or "expansion" (hash_join: cross
        #: joins, duplicate build keys seen)
        self.last_join_paths: List[str] = []
        #: beside it, each JoinNode's type (INNER, LEFT, FULL, SEMI, ANTI)
        #: and each AggregationNode's step (PARTIAL, FINAL, SINGLE), with
        #: how many grouping keys and how many aggregate calls it has
        self.last_join_types: List[str] = []
        self.last_agg_steps: List[str] = []
        self.last_agg_shapes: List[Tuple[int, int]] = []
        # Optional MemoryPool (exec/memory.py): static footprints
        # reserve against it at lower time (admission control BEFORE
        # execution — the TPU analog of MemoryPool.java's runtime
        # accounting); the engine frees per query.
        self.memory_pool = None
        self.pool_query_id: str = ""
        # EXPLAIN ANALYZE support (collect_stats session property):
        # per-node output row counts from the last execution.
        self.last_node_rows: Dict[int, int] = {}
        self._node_map: Dict[int, tuple] = {}   # nid -> (plan node, cap)
        #: the last lowering's stats node ids, once its closure is traced
        self.last_stats_box: List[int] = []

    def execute(self, plan: PlanNode) -> Page:
        import time
        budget = self.session["query_max_execution_time"]
        self._deadline = (time.time() + budget) if budget else None
        # stats maps are per query (islands accumulate into them)
        self.last_node_rows = {}
        self._node_map = {}
        plan = self._resolve_subqueries(plan)
        plan = self._prepare(plan)
        if isinstance(plan, TableWriterNode):
            return self._execute_writer(plan)
        return self._execute_prepared(plan)

    def _check_deadline(self):
        import time
        dl = getattr(self, "_deadline", None)
        if dl is not None and time.time() > dl:
            raise QueryTimeoutError(
                f"query exceeded query_max_execution_time "
                f"({self.session['query_max_execution_time']:.0f}s)")

    def _execute_writer(self, node: TableWriterNode) -> Page:
        """Writer root: run the source pipeline on device, then sink the
        rows host-side (ConnectorPageSink role) and emit the count row
        (TableWriterOperator's output contract). `column_names` maps the
        source outputs onto the target schema (missing columns
        NULL-fill), so a coordinator plan whose writer column order
        differs from the table layout still writes correctly."""
        page = self._execute_tree(node.source)
        rows = self._page_rows(page)
        schema = self.connector.schema(node.table)
        names = [c for c, _t in schema]
        cols = list(node.column_names) or list(page.names)
        if rows and len(rows[0]) != len(cols):
            raise ValueError(
                f"writer arity {len(rows[0])} != declared columns "
                f"{len(cols)}")
        if cols != names:
            unknown = [c for c in cols if c not in names]
            if unknown:
                raise ValueError(
                    f"writer columns not in table {node.table!r}: "
                    f"{unknown}")
            pos = {c: i for i, c in enumerate(cols)}
            rows = [tuple(r[pos[c]] if c in pos else None
                          for c in names) for r in rows]
        n = self.connector.append_rows(node.table, rows)
        out_col = Column.from_numpy(
            __import__("numpy").array([n], dtype="int64"),
            node.output_types[0])
        return Page.from_columns([out_col], 1, node.output_names)

    # ---- island-split execution ---------------------------------------
    # One XLA program per "fusion island" (a heavy operator plus the
    # row-wise Filter/Project chains feeding it) instead of one program
    # per plan, which bounds the size of each XLA program for
    # join-bearing plans. Device-resident Pages flow between islands —
    # no host round trip. This is the reference's own execution granularity
    # (operators connected by in-memory pages, Driver.java:310),
    # re-expressed as a handful of jit programs instead of ~38.
    _SPLIT_NODES = (JoinNode, AggregationNode, SortNode, TopNNode,
                    WindowNode, UnionAllNode, UnnestNode,
                    MarkDistinctNode, GroupIdNode)

    def _use_islands(self, plan: PlanNode) -> bool:
        if getattr(self, "_force_fused", False):
            return False

        # split only the shapes that blow up whole-plan compiles
        def splits(n):
            return isinstance(n, (JoinNode, WindowNode, UnionAllNode,
                                  UnnestNode, MarkDistinctNode,
                                  GroupIdNode)) or any(
                splits(c) for c in n.children() if c is not None)
        return splits(plan)

    def _island_of(self, plan: PlanNode):
        """(mini_plan, children): `plan`'s fusion island with descendant
        split-node subtrees replaced by PageInputNode slots. Cached by
        node identity (plans are reused across executions)."""
        cache = self.__dict__.setdefault("_island_cache", {})
        if len(cache) > 256:
            # bound the id-keyed memo (engines that re-plan per
            # execution would otherwise leak whole plan trees);
            # re-splitting is cheap and capacity ids are base-free
            cache.clear()
            self.__dict__.get("_island_alias", {}).clear()
        hit = cache.get(id(plan))
        if hit is not None:
            return hit[0], hit[1], hit[3]
        children: List[PlanNode] = []
        child_slots: Dict[int, int] = {}

        alias = self.__dict__.setdefault("_island_alias", {})

        def rec(n: PlanNode, is_root: bool) -> PlanNode:
            if n is None:
                return n
            if not is_root and isinstance(n, self._SPLIT_NODES):
                if id(n) in child_slots:
                    slot = child_slots[id(n)]
                else:
                    slot = len(children)
                    children.append(n)
                    child_slots[id(n)] = slot
                return PageInputNode(n.output_names, n.output_types,
                                     slot=slot)
            kids = n.children()
            if not kids:
                return n
            if isinstance(n, JoinNode):
                m = dataclasses.replace(
                    n, probe=rec(n.probe, False),
                    build=rec(n.build, False))
            elif isinstance(n, UnionAllNode):
                m = dataclasses.replace(
                    n, sources=tuple(rec(s, False) for s in n.sources))
            else:
                m = dataclasses.replace(n, source=rec(kids[0], False))
            # copy -> original identity, so EXPLAIN ANALYZE can project
            # per-island stats back onto the user-facing plan tree
            alias[id(m)] = id(n)
            return m

        mini = rec(plan, True)
        del rec          # it calls itself and closes over `self`: a cycle
        # stable per-island stats-id base: islands build in a
        # deterministic traversal order, so len(cache) is reproducible
        base = (len(cache) + 1) * 1_000_000
        cache[id(plan)] = (mini, children, plan, base)  # keep plan alive
        return mini, children, base

    def _execute_islands(self, plan: PlanNode) -> Page:
        """Optimistically dispatch the WHOLE island chain without
        syncing any island's counters, then resolve them all once: K
        islands cost one results-wait instead of K host<->device syncs
        (the per-island dispatch overhead the round-4 profile flagged).
        If any island's capacities grew (first
        execution of a novel plan; learned caps persist), the chain
        re-runs with the grown capacities."""
        profile = self.session["collect_stats"]
        self.last_island_profile: List[dict] = []

        for _round in range(8):
            run_memo: Dict[int, Page] = {}
            pendings: List[dict] = []

            def run(node: PlanNode) -> Page:
                if id(node) in run_memo:
                    return run_memo[id(node)]
                self._check_deadline()
                mini, children, base = self._island_of(node)
                pages = [run(c) for c in children]
                self._island_inputs = pages
                self._stats_base = base
                if profile:
                    # EXPLAIN ANALYZE: block per island for true wall
                    # times — the per-operator profile fused execution
                    # cannot produce (profiling trades away the async
                    # overlap, production runs keep it)
                    t0 = now()
                    out = self._execute_fused(mini)
                    with TRACER.span(None, "device_wait",
                                     sync="per_island"):
                        jax.block_until_ready(out)   # Page is a pytree
                    entry = {
                        "root": _kind(node),
                        "t0": t0,
                        "seconds": now() - t0,
                        "rows": int(out.num_rows),
                        "memory_bytes": self.last_memory_estimate,
                    }
                    self.last_island_profile.append(entry)
                    _M_OP_WALL.observe(entry["seconds"],
                                       operator=entry["root"])
                    _M_OP_ROWS.observe(entry["rows"],
                                       operator=entry["root"])
                else:
                    out, pending = self._dispatch_fused(mini)
                    pendings.append(pending)
                run_memo[id(node)] = out
                return out

            try:
                result = run(plan)
            finally:
                self._stats_base = 0
                # `run` calls itself, so it and the cells it closes over
                # (`self`, and through it the task's pages) are a
                # reference cycle: emptied, or every page of the fragment
                # waits on the device for the cyclic collector
                run_memo.clear()
                run = None
            if profile:
                return result
            resolved = self._await_counters(pendings)
            # growth first across ALL islands: a truncated upstream
            # island feeds garbage downstream, so downstream's deferred
            # error lanes must not raise until a clean converged round
            grew = False
            for p, arr in zip(pendings, resolved):
                if self._grow_caps(p, arr):
                    grew = True
            if not grew:
                for p, arr in zip(pendings, resolved):
                    self._finish_counters(p, arr)
                return result
            if self.memory_pool is not None:
                # the failed round's buffers are unwound on re-run —
                # release its reservations so retries never double-count
                for p in pendings:
                    self.memory_pool.free(self.pool_query_id,
                                          p["pool_prev"])
        raise RuntimeError("island capacity retry did not converge")

    def _await_counters(self, pendings):
        """Deadline-aware single wait for the whole island chain's
        counters: the sync runs on a helper thread while the query's
        time budget stays enforced (the chain dispatches in
        milliseconds, so this wait is where the compute time actually
        passes)."""
        with TRACER.span(None, "device_wait", sync="chain"):
            return self._sync_counters(pendings)

    def _sync_counters(self, pendings):
        import numpy as _np
        if getattr(self, "_deadline", None) is None:
            return [_np.asarray(p["needed"]) for p in pendings]
        import threading
        box = {}
        done = threading.Event()

        def waiter():
            try:
                box["v"] = [_np.asarray(p["needed"]) for p in pendings]
            except BaseException as e:   # noqa: BLE001 — re-raised below
                box["e"] = e
            finally:
                done.set()

        from presto_tpu.utils.threads import spawn
        spawn("exec", "counter-waiter", waiter)
        while not done.wait(0.5):
            self._check_deadline()
        if "e" in box:
            raise box["e"]
        return box["v"]

    def _execute_tree(self, plan: PlanNode) -> Page:
        if self._use_islands(plan):
            return self._execute_islands(plan)
        return self._execute_fused(plan)

    # ---- learned-capacity persistence ---------------------------------
    # Overflow retries recompile the whole program, and a cold compile
    # of a join program costs minutes. Persist the
    # converged capacity assignment per plan fingerprint so later
    # processes (bench children, worker restarts) lower at the right
    # capacities on the first attempt (the compiled-program analog of
    # the HBO row-count store).
    @staticmethod
    def _caps_store_path():
        import os
        p = os.environ.get("PRESTO_TPU_CAPS_CACHE")
        if p:
            return p
        return os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            ".caps_cache.json")

    def _plan_fingerprint(self, plan) -> str:
        import hashlib
        # salt with the connector identity/scale AND the scanned
        # tables' row counts: the same plan over SF0.01 and SF1 — or
        # over two different MemoryConnector datasets (sf=None) —
        # converges to different capacities
        sizes = []
        try:
            for t in sorted({n.table for n in self._walk_scans(plan)}):
                sizes.append((t, self.connector.table(t).num_rows))
        except Exception:   # noqa: BLE001 — salt is best-effort
            pass
        salt = (type(self.connector).__name__,
                getattr(self.connector, "sf", None), tuple(sizes))
        # over the plan with its lifted literals blanked (types kept):
        # every value of a literal learns and names one program
        return hashlib.sha1(
            (repr(salt) + repr(lift_plan(plan).plan)).encode()
        ).hexdigest()[:24]

    def program_name(self, plan: PlanNode) -> str:
        """What the device trace calls the island's program
        (`jit_<this>` on the modules' line): its root operator and the
        plan fingerprint the learned capacities are keyed by. Nothing
        of the query, task or run enters, so the name, which is part of
        XLA's cache key, repeats wherever the program does."""
        return f"presto_{_kind(plan)}_{self._plan_fingerprint(plan)[:8]}"

    @staticmethod
    def _walk_scans(plan):
        out = []

        def rec(n):
            if isinstance(n, TableScanNode):
                out.append(n)
            for c in n.children():
                if c is not None:
                    rec(c)
        rec(plan)
        return out

    def _plan_fingerprint_legacy(self, plan) -> str:
        import hashlib
        salt = (type(self.connector).__name__,
                getattr(self.connector, "sf", None))
        return hashlib.sha1(
            (repr(salt) + repr(plan)).encode()).hexdigest()[:24]

    def _load_caps(self, plan) -> Dict:
        import ast
        import json
        import os
        path = self._caps_store_path()
        if not os.path.exists(path):
            return {}
        try:
            with open(path) as f:
                data = json.load(f)
            raw = data.get(self._plan_fingerprint(plan))
            if raw is None:
                # migrate entries learned under the pre-row-count salt
                # (losing them would re-pay overflow-retry recompiles)
                raw = data.get(self._plan_fingerprint_legacy(plan), {})
            out = {}
            for k, v in raw.items():
                try:
                    key = int(k)
                except ValueError:
                    # exchange capacities are keyed (node_id, "cap"/
                    # "chunk") — persisted via str(), recovered here
                    key = ast.literal_eval(k)
                    if not isinstance(key, tuple):
                        continue
                out[key] = int(v)
            return out
        except Exception:   # noqa: BLE001 — cache is best-effort
            return {}

    def _save_caps(self, plan, caps: Dict) -> None:
        import json
        import os
        if not caps:
            return
        key = self._plan_fingerprint(plan)
        entry = {str(k): int(v) for k, v in caps.items()}
        # in-memory dedup: streaming paths and later tasks execute the
        # same plan again and again — only the FIRST convergence (or a
        # capacity change) touches the file
        saved = self.programs.saved
        if saved.get(key) == entry:
            return
        if len(saved) >= 512:       # the file's own bound, below
            saved.clear()
        saved[key] = entry
        path = self._caps_store_path()
        try:
            data = {}
            if os.path.exists(path):
                with open(path) as f:
                    data = json.load(f)
            if data.get(key) == entry:
                return
            data[key] = entry
            if len(data) > 512:
                # bound the cache file: evict oldest-inserted entries
                # (insertion order == json order) — stale fingerprints
                # only cost a re-learn, never wrong results
                for k in list(data)[:len(data) - 512]:
                    data.pop(k, None)
            tmp = f"{path}.{os.getpid()}.tmp"
            # lint: disable=spill-chokepoint — caps cache, not a spill
            with open(tmp, "w") as f:
                json.dump(data, f)
            os.replace(tmp, path)           # atomic vs concurrent writers
        except Exception:   # noqa: BLE001 — cache is best-effort
            pass

    def _dispatch_fused(self, plan: PlanNode, pool_prev: int = 0):
        """Lower + dispatch ONE program without syncing its counters.
        Returns (out_page, pending) where `pending` resolves later via
        `_resolve_counters` — island execution defers every island's
        sync to the end of the chain, so K islands cost ONE wait for
        results instead of K host<->device syncs."""
        # The literals whose value shapes nothing leave the plan and are
        # handed to the program when it is called (expr/params.py): what
        # is lowered, cached, named and learned from here on is the plan
        # with their places blank, one program for every value of them.
        lifted = lift_plan(plan)
        plan = lifted.plan
        # a copy of what the cache's owner has learned for this plan
        # (the caps file's, where the plan is new to it): concurrent tasks
        # of one plan share the learning, never a dict
        caps: Dict = self.programs.caps(plan, self._load_caps)
        # _lower is cheap (no tracing) and fills `caps` with its chosen
        # capacities, which completes the compilation cache key.
        lowered, scans, watch = self._lower(plan, caps, lifted.origin)
        stats_box = self.last_stats_box
        if self.memory_pool is not None:
            # admission control: swap the PREVIOUS attempt's
            # reservation for this one (capacity-grow retries must
            # not double-count); islands of one query accumulate —
            # their pages stay device-resident
            self.memory_pool.free(self.pool_query_id, pool_prev)
            self.memory_pool.reserve(self.pool_query_id,
                                     self.last_memory_estimate)
            pool_prev = self.last_memory_estimate
        # Key and capacities are everything _lower read: the plan, the
        # input specs (each with the capacity its page has: the closure
        # holds integers derived from them), the hooks' class, and of the
        # session collect_stats with the stats ids' base. Whoever reaches
        # equal ones, in this task or a later one, runs the same program.
        collect_stats = bool(self.session["collect_stats"])
        key = (type(self), plan, tuple(scans), collect_stats,
               getattr(self, "_stats_base", 0) if collect_stats else 0)
        lowered_at = tuple(sorted(caps.items(), key=repr))

        def make() -> Program:
            # stats_box is filled at the program's first execution
            # (trace time fixes the node-id order for its lifetime).
            program = self._wrap(lowered)
            program.__name__ = program.__qualname__ = \
                self.program_name(plan)
            # what its `dispatch` spans say of the program ("+" joins
            # the operators: a comma would end the value in the
            # profiler's encoding of an annotation's metadata)
            about = {"program": "jit_" + program.__name__,
                     "root": _kind(plan),
                     "operators": "+".join(_operators(plan))}
            if self.last_join_paths:
                about["join_paths"] = "+".join(self.last_join_paths)
                about["join_types"] = "+".join(self.last_join_types)
            if self.last_agg_steps:
                about["agg_steps"] = "+".join(self.last_agg_steps)
                keys, calls = zip(*self.last_agg_shapes)
                about["group_keys"] = "+".join(map(str, keys))
                about["aggregates"] = "+".join(map(str, calls))
            return Program(jax.jit(program), lowered_at, scans, watch,
                           stats_box, about)

        # on a hit this lowering's closure is dropped for the kept one's
        program, first_call = self.programs.program(key, lowered_at, make)
        if first_call:
            self._note_compile(plan)
        pages = [self._fetch(s) for s in program.scans]
        # where the cache had no such program, the call is Python trace +
        # lowering + compile or cache read + enqueue; else it only enqueues
        with TRACER.span(None, "dispatch", first_call=first_call,
                         params=len(lifted.values), **program.about):
            out, needed = program(pages, lifted.values)
        pending = {"plan": plan, "caps": caps, "watch": program.watch,
                   "needed": needed, "stats_box": program.stats_box,
                   "pool_prev": pool_prev}
        return out, pending

    def _grow_caps(self, pending, needed) -> bool:
        """Apply observed capacity needs; True = re-run required."""
        caps = pending["caps"]
        grew = False
        for nid, need in zip(pending["watch"], needed):
            need = int(need)
            if need > caps[nid]:
                caps[nid] = bucket_capacity(need)
                grew = True
        if grew:
            self.programs.learn(pending["plan"], caps)
        return grew

    def _anneal_caps(self, pending, needed) -> None:
        """Shrink learned capacities back toward the observed need.

        Growth is overflow-driven and monotone, so one oversized first
        guess (an exchange sized at twice its upstream capacity, a join
        fanout hint that never materializes) pins every later run to
        that bucket — and program cost scales with capacity, not rows.
        Each converged run updates a per-counter peak and re-buckets
        the cap at peak + 25% headroom; peaks are monotone, so the cap
        steps down to the true requirement and stays there instead of
        flip-flopping. The peak is kept under the plan with its lifted
        literals blanked, so it is the largest need any literal value
        has shown. An undershoot on later, larger data, or on a first
        less selective literal, is always recoverable: every watched
        counter reports its unclamped need and rides the normal
        overflow-retry loop (one re-lowering at the next bucket)."""
        caps = pending["caps"]
        plan = pending["plan"]
        lowered = []
        for nid, need in zip(pending["watch"], needed):
            if isinstance(nid, int) and nid < 0:
                continue    # merge-join duplicate flags, not capacities
            peak = self.programs.peak(plan, nid, int(need))
            tgt = bucket_capacity(max(peak + (peak >> 2), 64))
            if tgt < caps[nid]:
                caps[nid] = tgt
                lowered.append(nid)
        self.programs.learn(plan, caps, lowered)

    def _finish_counters(self, pending, needed) -> None:
        """Converged program: raise checked-arithmetic errors, record
        stats, persist the learned capacities."""
        from presto_tpu.expr import errors as _E
        watch = pending["watch"]
        _E.raise_for_mask(int(needed[len(watch)]))
        self._anneal_caps(pending, needed)
        stats_box = pending["stats_box"]
        if stats_box:
            stats = needed[len(watch) + 1:]
            node_map = getattr(self, "_node_map", {}) or {}
            for nid, r in zip(stats_box, stats):
                self.last_node_rows[nid] = int(r)
                entry = node_map.get(nid)
                op = (type(entry[0]).__name__.replace("Node", "")
                      if entry else "?")
                _M_OP_ROWS.observe(int(r), operator=op)
        self._save_caps(pending["plan"], pending["caps"])

    def _resolve_counters(self, pending) -> bool:
        """Sync + resolve one dispatched program (the single-program
        path): returns True when a re-run is required."""
        import numpy as _np
        # one program, one wait: under collect_stats that is a wait an
        # island (the profiled branch of _execute_islands comes here)
        sync = "per_island" if self.session["collect_stats"] else "chain"
        with TRACER.span(None, "device_wait", sync=sync):
            needed = _np.asarray(pending["needed"])   # the sync point
        if self._grow_caps(pending, needed):
            return True
        self._finish_counters(pending, needed)
        return False

    def _execute_fused(self, plan: PlanNode) -> Page:
        # Learned capacities persist per plan: overflow retries and
        # merge-join duplicate fallbacks are paid once, not per execution.
        pool_prev = 0                 # this plan's live reservation
        for _attempt in range(8):
            out, pending = self._dispatch_fused(plan, pool_prev)
            pool_prev = pending["pool_prev"]
            if not self._resolve_counters(pending):
                return out
        raise RuntimeError("capacity retry loop did not converge")

    # ---- hooks overridden by the distributed executor ------------------
    def _prepare(self, plan: PlanNode) -> PlanNode:
        return plan

    def _execute_prepared(self, plan: PlanNode) -> Page:
        """Run an already-prepared plan (the distributed executor splits
        it into fragments here; EXPLAIN ANALYZE enters through this hook
        so it measures the real execution shape)."""
        return self._execute_tree(plan)

    def _note_compile(self, plan: PlanNode) -> None:
        """A new program was added to the compile cache (mesh executor
        counts fragment compiles here)."""

    def _wrap(self, fn: Callable) -> Callable:
        return fn

    def _page_rows(self, page: Page):
        return page.to_pylist()

    def _scan_rows(self, node) -> int:
        return self.connector.table(node.table).num_rows

    # Trace-time hooks: static here, so that a lowered closure holds no
    # executor (a program outlives the task that made it); the mesh
    # executor overrides them with methods and keeps a cache of its own.
    @staticmethod
    def _unique_ids(p: Page) -> jnp.ndarray:
        return jnp.arange(p.capacity, dtype=jnp.int64)

    @staticmethod
    def _finish_agg(node, out: Page) -> Page:
        return out

    @staticmethod
    def _finish_values(out: Page) -> Page:
        return out

    def _remote_input(self, node, scans):
        raise RuntimeError(
            "cut exchange in a single-process plan (fragments are only "
            "executed separately by the distributed executor)")

    def _remote_source(self, node, scans):
        raise RuntimeError(
            "RemoteSourceNode outside a protocol-driven task (the worker "
            "TaskManager binds remote splits before execution)")

    def _lower_exchange(self, node, nid, src, cap, caps, watch, _needed):
        """Single-process executor: an exchange is a no-op relabel (all
        rows already live in one page). The distributed executor overrides
        this with ICI collectives."""
        def out_fn(pages, node=node):
            p = src(pages)
            return Page(p.columns, p.num_rows, node.output_names)
        return out_fn, cap

    # ------------------------------------------------------------------
    def _fetch(self, s) -> Page:
        if isinstance(s, PageInputSpec):
            return self._island_inputs[s.slot]
        # host -> device: the connector's arrays (or the task's splits
        # of them) become one device page
        with TRACER.span(None, "upload", table=s.table) as sp:
            page = self._scan_page(s)
            sp.attributes["bytes"] = page_nbytes(page) \
                - sp.attributes.get("resident", 0)
        return page

    def _scan_page(self, s: ScanSpec) -> Page:
        t = self.connector.table(s.table)
        return t.page(columns=list(s.columns), capacity=s.capacity)

    def _resolve_subqueries(self, plan: PlanNode) -> PlanNode:
        """Pre-execute scalar subqueries (uncorrelated), substituting
        literals (reference role: EnforceSingleRowOperator +
        coordinator-side subquery planning)."""
        from presto_tpu.sql.analyzer import Subquery

        def rewrite_expr(e: RowExpression) -> RowExpression:
            if isinstance(e, Subquery):
                page = self.execute(e.plan)
                rows = self._page_rows(page)
                if len(rows) != 1:
                    raise RuntimeError(
                        f"scalar subquery returned {len(rows)} rows")
                v = rows[0][0]
                if e.type.is_decimal and v is not None:
                    v = int(round(v * 10 ** e.type.scale))
                return Literal(v, e.type)
            if isinstance(e, Call):
                return dataclasses.replace(
                    e, args=tuple(rewrite_expr(a) for a in e.args))
            if isinstance(e, SpecialForm):
                return dataclasses.replace(
                    e, args=tuple(rewrite_expr(a) for a in e.args))
            return e

        def has_subquery(e) -> bool:
            if isinstance(e, Subquery):
                return True
            return any(has_subquery(c) for c in e.children())

        def rewrite(node: PlanNode) -> PlanNode:
            kids = tuple(rewrite(c) for c in node.children())
            repl = {}
            if isinstance(node, FilterNode):
                repl = {"source": kids[0]}
                if has_subquery(node.predicate):
                    repl["predicate"] = rewrite_expr(node.predicate)
            elif isinstance(node, ProjectNode):
                repl = {"source": kids[0]}
                if any(has_subquery(e) for e in node.expressions):
                    repl["expressions"] = tuple(
                        rewrite_expr(e) for e in node.expressions)
            elif isinstance(node, JoinNode):
                repl = {"probe": kids[0], "build": kids[1]}
                if node.filter is not None and has_subquery(node.filter):
                    repl["filter"] = rewrite_expr(node.filter)
            elif kids:
                names = [f.name for f in dataclasses.fields(node)]
                if "sources" in names:      # UnionAllNode and friends
                    repl = {"sources": kids}
                elif "source" in names:
                    repl = {"source": kids[0]}
            if all(same_objects(getattr(node, k), v)
                   for k, v in repl.items()):
                # nothing under it changed: the plan keeps its identity,
                # and with it its islands and their stats ids, so that a
                # plan executed once a chunk is one program, not one a
                # chunk (`_island_of` is keyed by identity)
                return node
            return dataclasses.replace(node, **repl)

        try:
            return rewrite(plan)
        finally:
            # they call themselves and close over `self`: a cycle
            del rewrite, rewrite_expr

    # ------------------------------------------------------------------
    def _lower(self, plan: PlanNode, caps: Dict[int, int],
               origin: Optional[Dict[int, PlanNode]] = None
               ) -> Tuple[Callable, List[ScanSpec], List[int]]:
        """Build (traced_fn(pages, params) -> (Page, needed[]), scan
        specs, watched node ids). Node ids are stable pre-order
        positions. `params` are the values of the plan's `Param`s;
        `origin` maps a node that `lift_plan` rebuilt to the statement's
        own, which is what the stats maps keep."""
        origin = origin or {}
        scans: List[ScanSpec] = []
        watch: List[int] = []
        counter = [0]
        # CAPACITY ids must be identical on every lowering of the same
        # (mini) plan — they key the persisted caps cache, and a base
        # offset would orphan learned TPU capacities across re-plans.
        # STATS ids additionally carry the island's base so row counts
        # from different islands of one query never collide.
        base = getattr(self, "_stats_base", 0)

        def node_id(_n) -> int:
            counter[0] += 1
            return counter[0]

        # Shared subtrees (mark joins reference the probe pipeline twice)
        # must lower and evaluate ONCE: memoize by node identity, and cache
        # each node's output per run so trace-time Python also runs once.
        memo: Dict[int, Tuple[Callable, int]] = {}
        run_cache: Dict[int, Page] = {}

        mem_bytes = [0]
        collect_stats = bool(self.session["collect_stats"])
        _node_rows: List = []
        stats_box: List[int] = []
        # what the traced closures call of the executor, by value
        finish_agg = self._finish_agg
        finish_values = self._finish_values
        unique_ids = self._unique_ids
        if base == 0:
            self._node_map = {}
        # island mode (base > 0): maps ACCUMULATE across the query's
        # islands; execute() resets them per query

        def build(node: PlanNode):
            key = id(node)
            if key in memo:
                return memo[key]
            nid_stats = base + counter[0] + 1  # id build_inner assigns
            fn, cap = build_inner(node)
            mem_bytes[0] += cap * _row_bytes(node.output_types)
            self._node_map[nid_stats] = (origin.get(id(node), node), cap)

            def cached(pages, fn=fn, key=key, nid=nid_stats,
                       kind=_kind(node)):
                if key in run_cache:
                    return run_cache[key]
                # traced here: the operations' metadata carries the
                # operator (and, nested, the operators it feeds)
                with jax.named_scope(kind):
                    out = fn(pages)
                if collect_stats:
                    _node_rows.append((nid, out.num_rows))
                run_cache[key] = out
                return out
            memo[key] = (cached, cap)
            return memo[key]

        def build_inner(node: PlanNode):
            nid = node_id(node)
            if isinstance(node, PageInputNode):
                idx = len(scans)
                cap = self._island_inputs[node.slot].capacity
                scans.append(PageInputSpec(node.slot, cap))
                return (lambda pages: pages[idx]), cap
            if isinstance(node, TableScanNode):
                # Exact row count (generation is cached), not the planner
                # estimate — an under-estimated bucket would truncate rows.
                cap = caps.get(nid) or bucket_capacity(
                    self._scan_rows(node))
                idx = len(scans)
                scans.append(ScanSpec(node.table, node.columns, cap))
                return lambda pages: pages[idx], cap
            if isinstance(node, RemoteSourceNode):
                return self._remote_source(node, scans)
            if isinstance(node, ValuesNode):
                def values_fn(pages, node=node):
                    n = len(node.rows)
                    cols = tuple(
                        Column.from_numpy(
                            __import__("numpy").array(
                                [r[i] for r in node.rows]), t)
                        for i, t in enumerate(node.output_types))
                    return finish_values(
                        Page(cols, jnp.asarray(n, jnp.int32), ()))
                return values_fn, bucket_capacity(max(len(node.rows), 1))
            if isinstance(node, FilterNode):
                src, cap = build(node.source)
                pred = compile_expr(node.predicate)

                def filter_fn(pages):
                    p = src(pages)
                    c = pred(p)
                    return compact(p, ~c.nulls & c.values.astype(bool))
                return filter_fn, cap
            if isinstance(node, ProjectNode):
                src, cap = build(node.source)
                exprs = [compile_expr(e) for e in node.expressions]

                def project_fn(pages, node=node):
                    p = src(pages)
                    cols = tuple(ex(p) for ex in exprs)
                    return Page(cols, p.num_rows, node.output_names)
                return project_fn, cap
            if isinstance(node, AggregationNode):
                # Fuse the whole Filter/Project chain below the aggregation
                # into it: projections are row-wise column rewrites (row
                # count unchanged) and filters become a row mask consumed
                # by the aggregation — so the pipeline never compacts, and
                # never pays a sort. This is the reference's
                # ScanFilterAndProject -> HashAggregation pipeline fusion
                # (ScanFilterAndProjectOperator.java:67), taken further
                # because XLA fuses the mask into the reductions.
                steps = []            # bottom-up (kind, compiled payload)
                source = node.source
                while isinstance(source, (FilterNode, ProjectNode)):
                    if isinstance(source, FilterNode):
                        steps.append(("filter",
                                      compile_expr(source.predicate), None))
                    else:
                        steps.append(
                            ("project",
                             [compile_expr(e) for e in source.expressions],
                             source.output_names))
                    source = source.source
                steps.reverse()
                src, cap = build(source)
                hint = node.group_count_hint \
                    or self.session["group_count_hint"]
                out_cap = caps.get(nid) or min(
                    cap, bucket_capacity(hint))
                if not node.group_fields:
                    out_cap = 256
                caps[nid] = out_cap
                watch.append(nid)
                agg_steps.append(node.step.name)
                agg_shapes.append((len(node.group_fields), len(node.aggs)))

                def agg_fn(pages, node=node, out_cap=out_cap, steps=steps):
                    p = src(pages)
                    mask = None
                    for kind, payload, names in steps:
                        if kind == "filter":
                            c = payload(p)
                            m = ~c.nulls & c.values.astype(bool)
                            mask = m if mask is None else (mask & m)
                        else:
                            cols = tuple(ex(p) for ex in payload)
                            p = Page(cols, p.num_rows, names)
                    out, true_groups = grouped_aggregate(
                        p, node.group_fields, node.aggs, out_cap,
                        row_mask=mask)
                    _needed.append(true_groups)
                    return finish_agg(node, out)
                return agg_fn, out_cap
            if isinstance(node, JoinNode):
                psrc, pcap = build(node.probe)
                bsrc, bcap = build(node.build)
                # ANTI_EXISTS is an ANTI join to whoever reads the span
                join_types.append(node.join_type.name.split("_")[0])
                if node.join_type in (JoinType.SEMI, JoinType.ANTI,
                                      JoinType.ANTI_EXISTS):
                    # Merge path: duplicates can't change a match flag,
                    # so no fallback is ever needed here.
                    join_paths.append("merge")
                    out_cap = pcap
                    if not node.emit_flag:
                        # A filtering semi join may keep a sliver of its
                        # probe (TPC-H Q18: 378 of 6M rows): the
                        # survivors' capacity is learned like a join's,
                        # so what runs above it is not paid at the
                        # probe's size. It starts at the probe's (no
                        # overflow), anneals to the count seen, and an
                        # undershoot re-runs through the overflow loop.
                        out_cap = caps.get(nid) or pcap
                        caps[nid] = out_cap
                        watch.append(nid)

                    def semi_fn(pages, node=node, out_cap=out_cap):
                        p = psrc(pages)
                        b = bsrc(pages)
                        out, _dup, _m = merge_join(
                            p, b, node.probe_keys, node.build_keys,
                            node.join_type.value)
                        if node.emit_flag:
                            # Protocol SemiJoinNode contract: keep every
                            # probe row, expose the flag column.
                            return Page(out.columns, out.num_rows,
                                        node.output_names)
                        keep = out.columns[-1].values.astype(bool) \
                            & out.row_valid()
                        _needed.append(jnp.sum(keep))
                        return compact(
                            Page(out.columns[:-1], out.num_rows,
                                 node.output_names), keep, out_cap)
                    return semi_fn, out_cap

                # Unique-build merge join first (two sorts + scans; the
                # TPU-fast path — TPC-H joins are FK joins). The dup
                # counter rides the generic overflow-retry loop under the
                # negated node id: any duplicate live build key re-lowers
                # onto the expansion hash_join below.
                use_merge = (bool(node.probe_keys)
                             and node.join_type in (JoinType.INNER,
                                                    JoinType.LEFT,
                                                    JoinType.FULL)
                             and caps.get(-nid, 0) == 0)
                join_paths.append("merge" if use_merge else "expansion")
                if use_merge:
                    caps[-nid] = 0
                    watch.append(-nid)

                    def mjoin_fn(pages, node=node):
                        p = psrc(pages)
                        b = bsrc(pages)
                        residual = (compile_expr(node.filter)
                                    if node.filter is not None else None)
                        if (residual is not None
                                and node.join_type == JoinType.LEFT):
                            # Residual failure demotes a match to a
                            # null-extension (SQL outer-join ON clause):
                            # evaluate over the pre-filter join, then
                            # null out the build side where it fails.
                            out, dup, match = merge_join(
                                p, b, node.probe_keys, node.build_keys,
                                "left")
                            _needed.append(dup)
                            out = Page(out.columns, out.num_rows,
                                       node.output_names)
                            c = residual(out)
                            ok = match & ~c.nulls & c.values.astype(bool)
                            cols = list(out.columns[:len(p.columns)])
                            for bc in out.columns[len(p.columns):]:
                                sent = jnp.asarray(
                                    bc.type.null_sentinel(),
                                    dtype=bc.values.dtype)
                                cols.append(Column(
                                    jnp.where(ok, bc.values, sent),
                                    jnp.where(ok, bc.nulls, True),
                                    bc.type, bc.dictionary))
                            return Page(tuple(cols), out.num_rows,
                                        node.output_names)
                        out, dup, _match = merge_join(
                            p, b, node.probe_keys, node.build_keys,
                            node.join_type.value)
                        _needed.append(dup)
                        out = Page(out.columns, out.num_rows,
                                   node.output_names)
                        if node.filter is not None:
                            if node.join_type == JoinType.FULL:
                                raise NotImplementedError(
                                    "residual filter on full outer join")
                            c = compile_expr(node.filter)(out)
                            out = compact(out,
                                          ~c.nulls & c.values.astype(bool))
                        return out
                    # FULL appends the unmatched build rows: capacity grows
                    out_cap = pcap + (bcap if node.join_type
                                      == JoinType.FULL else 0)
                    return mjoin_fn, out_cap

                fan = max(node.fanout_hint, 1.0)
                out_cap = caps.get(nid) or bucket_capacity(
                    min(int(pcap * fan), 2**26))
                caps[nid] = out_cap
                watch.append(nid)

                if node.join_type == JoinType.FULL:
                    raise NotImplementedError(
                        "full outer join with duplicate build keys (the "
                        "expansion path has no full-outer form yet)")

                def join_fn(pages, node=node, out_cap=out_cap):
                    p = psrc(pages)
                    b = bsrc(pages)
                    out, total = hash_join(
                        p, b, node.probe_keys, node.build_keys, out_cap,
                        node.join_type.value)
                    _needed.append(total)
                    out = Page(out.columns, out.num_rows,
                               node.output_names)
                    if node.filter is not None:
                        c = compile_expr(node.filter)(out)
                        if node.join_type == JoinType.LEFT:
                            raise NotImplementedError(
                                "residual ON filter on a LEFT join whose "
                                "build side has duplicate keys (the "
                                "expansion fallback cannot null-extend "
                                "per probe row yet; build-side-only "
                                "conditions are pre-filtered by the "
                                "planner and never reach here)")
                        out = compact(out,
                                      ~c.nulls & c.values.astype(bool))
                    return out
                return join_fn, out_cap
            if isinstance(node, GroupIdNode):
                src, cap = build(node.source)
                nsets = len(node.grouping_sets)
                out_cap = nsets * cap
                # membership[s, c]: does column c survive in set s?
                # (non-key columns always do)
                member_np = __import__("numpy").ones(
                    (nsets, node.arity - 1), dtype=bool)
                for s, keep in enumerate(node.grouping_sets):
                    for c in node.key_fields:
                        member_np[s, c] = c in keep

                def gid_fn(pages, node=node, nsets=nsets,
                           member_np=member_np):
                    p = src(pages)
                    n = p.num_rows
                    ocap = nsets * p.capacity
                    r = jnp.arange(ocap, dtype=jnp.int32)
                    n1 = jnp.maximum(n, 1)
                    set_id = jnp.clip(r // n1, 0, nsets - 1)
                    srci = r - set_id * n1
                    valid = r < nsets * n
                    member = jnp.asarray(member_np)
                    cols = []
                    for ci, c in enumerate(p.columns):
                        keep = member[:, ci][set_id] & valid
                        vals = jnp.take(c.values, srci, mode="clip")
                        nulls = jnp.take(c.nulls, srci, mode="clip")
                        sent = jnp.asarray(c.type.null_sentinel(),
                                           dtype=vals.dtype)
                        cols.append(Column(
                            jnp.where(keep, vals, sent),
                            jnp.where(keep, nulls, True),
                            c.type, c.dictionary))
                    gsent = jnp.asarray(
                        node.output_types[-1].null_sentinel(), jnp.int64)
                    gid = Column(
                        jnp.where(valid, set_id.astype(jnp.int64), gsent),
                        ~valid, node.output_types[-1], None)
                    return Page(tuple(cols) + (gid,),
                                (nsets * n).astype(jnp.int32),
                                node.output_names)
                return gid_fn, out_cap
            if isinstance(node, AssignUniqueIdNode):
                src, cap = build(node.source)

                def rowid_fn(pages, node=node):
                    p = src(pages)
                    ids = unique_ids(p)
                    col = Column(ids, ~p.row_valid(),
                                 node.output_types[-1], None)
                    return Page(p.columns + (col,), p.num_rows,
                                node.output_names)
                return rowid_fn, cap
            if isinstance(node, TableWriterNode):
                src, cap = build(node.source)

                def writer_fn(pages, node=node):
                    # the jit pipeline produces the page; the sink write
                    # is a HOST side-effect (ConnectorPageSink role) —
                    # legal here because jit tracing happens once and the
                    # actual write runs per execution via io_callback-free
                    # host interpretation: the executor runs this whole
                    # closure eagerly when the plan root is a writer (see
                    # execute()); inside jit it is rejected below.
                    raise NotImplementedError(
                        "TableWriterNode inside a jit fragment — the "
                        "engine executes writer roots host-side")
                return writer_fn, cap
            if isinstance(node, MarkDistinctNode):
                src, cap = build(node.source)

                def mark_fn(pages, node=node):
                    from presto_tpu.ops.mark_distinct import mark_distinct
                    p = src(pages)
                    out = mark_distinct(p, node.key_fields,
                                        node.output_names[-1])
                    return Page(out.columns, out.num_rows,
                                node.output_names)
                return mark_fn, cap
            if isinstance(node, UnionAllNode):
                built = [build(s) for s in node.sources]
                out_cap = sum(c for _f, c in built)

                def union_fn(pages, node=node, built=built,
                             out_cap=out_cap):
                    from presto_tpu.data.column import merge_string_dicts
                    ps = [f(pages) for f, _c in built]
                    cols = []
                    for ci, t in enumerate(node.output_types):
                        branch = [p.columns[ci] for p in ps]
                        dicts = [c.dictionary for c in branch]
                        d0 = dicts[0]
                        if t.is_string and any(d is not d0
                                               for d in dicts):
                            # per-source dictionaries differ: merge at
                            # trace time (dicts are static aux), remap
                            # codes with constant tables
                            union_d, remaps = merge_string_dicts(dicts)
                            vals = jnp.concatenate([
                                (jnp.take(jnp.asarray(r), c.values,
                                          mode="clip") if len(r)
                                 else c.values)
                                for c, r in zip(branch, remaps)])
                            d0 = union_d
                        else:
                            vals = jnp.concatenate(
                                [c.values for c in branch])
                        nulls = jnp.concatenate(
                            [c.nulls for c in branch])
                        cols.append(Column(vals, nulls, t, d0))
                    # each source's valid rows sit at its own capacity
                    # offset; declare everything in-range, then compact
                    # squeezes the survivors dense and sets num_rows
                    keep = jnp.concatenate([p.row_valid() for p in ps])
                    out = Page(tuple(cols),
                               jnp.asarray(out_cap, jnp.int32),
                               node.output_names)
                    return compact(out, keep)
                return union_fn, out_cap
            if isinstance(node, UnnestNode):
                src, cap = build(node.source)
                fan = max(node.fanout_hint, 1.0)
                out_cap = caps.get(nid) or bucket_capacity(
                    min(int(cap * fan), 2**26))
                caps[nid] = out_cap
                watch.append(nid)

                def unnest_fn(pages, node=node, out_cap=out_cap):
                    from presto_tpu.ops.unnest import unnest_page
                    p = src(pages)
                    out, total = unnest_page(
                        p, node.replicate_fields, node.unnest_fields,
                        out_cap, node.with_ordinality, node.output_names)
                    _needed.append(total)
                    return out
                return unnest_fn, out_cap
            if isinstance(node, WindowNode):
                src, cap = build(node.source)

                def window_fn(pages, node=node):
                    from presto_tpu.ops.window import window_page
                    p = src(pages)
                    out = window_page(p, node.partition_fields,
                                      node.order_keys, node.specs)
                    return Page(out.columns, out.num_rows,
                                node.output_names)
                return window_fn, cap
            if isinstance(node, SortNode):
                src, cap = build(node.source)
                return (lambda pages: sort_page(src(pages), node.keys)), cap
            if isinstance(node, TopNNode):
                src, cap = build(node.source)
                return (lambda pages: top_n(src(pages), node.keys,
                                            node.count)), cap
            if isinstance(node, LimitNode):
                src, cap = build(node.source)
                return (lambda pages: limit_page(src(pages),
                                                 node.count)), cap
            if isinstance(node, ExchangeNode):
                if node.source is None:      # cut: reads another fragment
                    src, cap = self._remote_input(node, scans)
                else:
                    src, cap = build(node.source)
                return self._lower_exchange(node, nid, src, cap, caps,
                                            watch, _needed)
            if isinstance(node, OutputNode):
                src, cap = build(node.source)

                def out_fn(pages, node=node):
                    p = src(pages)
                    return Page(p.columns, p.num_rows, node.output_names)
                return out_fn, cap
            raise NotImplementedError(f"lowering {type(node).__name__}")

        _needed: List = []
        join_paths: List[str] = []
        join_types: List[str] = []
        agg_steps: List[str] = []
        agg_shapes: List[Tuple[int, int]] = []
        root, _cap = build(plan)
        # build and build_inner call each other and close over `self`: a
        # reference cycle that would keep the executor, and the pages it
        # holds, on the device until the cyclic collector runs. Nothing
        # traced refers to them; emptying their cells breaks the cycle.
        del build, build_inner
        self.last_memory_estimate = mem_bytes[0]
        self.last_join_paths = join_paths
        self.last_join_types = join_types
        self.last_agg_steps = agg_steps
        self.last_agg_shapes = agg_shapes
        self.last_stats_box = stats_box
        if self.memory_limit_bytes is not None \
                and mem_bytes[0] > self.memory_limit_bytes:
            raise MemoryLimitExceeded(mem_bytes[0],
                                      self.memory_limit_bytes)

        # the closures share the three lists above while a trace runs:
        # a retrace (another dictionary on a string column) waits its turn
        tracing = threading.Lock()

        def run(pages, params=()):
            from presto_tpu.expr import errors as E
            with tracing:
                try:
                    with E.collecting() as coll, binding(params):
                        out = root(pages)
                        err = coll.combined()
                    # The checked-arithmetic error lane rides right after
                    # the capacity counters, then stats, in one stacked
                    # array (a single host transfer); the stats node-id
                    # order is fixed at trace time.
                    stats_box[:] = [nid for nid, _ in _node_rows]
                    extras = [r for _nid, r in _node_rows]
                    all_counters = list(_needed) + [err] + extras
                finally:
                    # a kept program keeps no tracer of its last trace
                    _needed.clear()
                    run_cache.clear()
                    _node_rows.clear()
            counters = jnp.stack(
                [jnp.asarray(n, jnp.int64) for n in all_counters])
            return out, counters

        return run, scans, watch
