"""EXPLAIN ANALYZE — the executed plan annotated with measured stats.

Reference roles: the QueryStats -> OperatorStats tree
(presto-main-base/.../operator/OperatorStats.java) rendered by
ExplainAnalyzeOperator. TPU reinterpretation: operators fuse into one XLA
program per fragment, so per-operator WALL TIME does not exist — what is
real and reported is per-node output cardinality (traced counters riding
the overflow-counter transfer), static capacity/memory footprint per
node, and per-execution wall/compile time. Fused nodes (filter/project
chains absorbed into aggregations) are marked as such.
"""

from __future__ import annotations

import time
from typing import Dict

from presto_tpu.exec.executor import _row_bytes
from presto_tpu.plan import nodes as P


def _detail(node) -> str:
    if isinstance(node, P.TableScanNode):
        return f" {node.table}{list(node.columns)}"
    if isinstance(node, P.FilterNode):
        return f" {node.predicate}"
    if isinstance(node, P.AggregationNode):
        return (f" keys={list(node.group_fields)} "
                f"aggs={[a.kind for a in node.aggs]} "
                f"step={node.step.value}")
    if isinstance(node, P.JoinNode):
        return (f" {node.join_type.value} "
                f"probe{list(node.probe_keys)}=build{list(node.build_keys)}")
    if isinstance(node, P.WindowNode):
        return (f" partition={list(node.partition_fields)} "
                f"fns={[s.kind for s in node.specs]}")
    if isinstance(node, (P.TopNNode, P.LimitNode)):
        return f" n={node.count}"
    if isinstance(node, P.ExchangeNode):
        return f" {node.partitioning.value} keys={list(node.keys)}"
    return ""


def render_analyzed(plan, node_map: Dict[int, tuple],
                    node_rows: Dict[int, int], wall_s: float,
                    memory_bytes: int, alias: Dict[int, int] = None,
                    island_profile=None, mesh_stats=None,
                    est=None) -> str:
    """Annotate the plan tree with executed row counts + footprints.
    `alias` maps island-copy node identities back to the user-facing
    plan's nodes (island mode rebuilds subtrees with
    dataclasses.replace); `island_profile` carries per-island wall
    times — the per-operator profile fused execution cannot have.
    `est` (node -> estimated rows) puts the planner's estimate next to
    each observed count so HBO drift is visible in one rendering."""
    alias = alias or {}
    by_identity = {}
    for nid, (n, cap) in node_map.items():
        by_identity[alias.get(id(n), id(n))] = (nid, cap)
    lines = []

    def est_of(node) -> str:
        if est is None:
            return ""
        try:
            return f"est_rows={int(est(node))} "
        except Exception:       # noqa: BLE001 — estimate must never fail EXPLAIN
            return ""

    def walk(node, depth):
        pad = "  " * depth
        name = type(node).__name__.replace("Node", "")
        info = by_identity.get(id(node))
        if info is None:
            annot = f"(fused into parent) {est_of(node)}".rstrip()
        else:
            nid, cap = info
            rows = node_rows.get(nid)
            bytes_ = cap * _row_bytes(node.output_types)
            annot = (f"rows={rows if rows is not None else '?'} "
                     f"{est_of(node)}"
                     f"cap={cap} ~{bytes_ // 1024} KiB")
        lines.append(f"{pad}{name}{_detail(node)}  [{annot}]")
        for c in node.children():
            if c is not None:
                walk(c, depth + 1)

    walk(plan, 0)
    if island_profile:
        lines.append("-- island profile (one XLA program per heavy "
                     "operator):")
        for i, p in enumerate(island_profile):
            lines.append(
                f"   island {i}: {p['root']}  "
                f"{p['seconds'] * 1000:.1f} ms  rows={p['rows']}  "
                f"~{p['memory_bytes'] // (1 << 20)} MiB")
    if mesh_stats:
        # ICI-mesh analog of the cluster renderer's "Exchange:" line
        # (server/cluster.py): what the device exchanges actually cost.
        lines.append(
            f"Mesh: ndev={mesh_stats['ndev']} "
            f"fragments={mesh_stats['fragments']} "
            f"collectives={mesh_stats['collectives']} "
            f"wire={mesh_stats['wire_bytes'] // 1024} KiB "
            f"overflow_retries={mesh_stats['overflow_retries']} "
            f"fragment_compiles={mesh_stats['fragment_compiles']}")
    lines.append(f"-- wall {wall_s * 1000:.1f} ms, "
                 f"plan footprint ~{memory_bytes // (1 << 20)} MiB")
    return "\n".join(lines)


def explain_analyze(engine, sql: str) -> str:
    """Execute `sql` with stats collection and render the analyzed plan
    (reference: EXPLAIN ANALYZE via ExplainAnalyzeOperator)."""
    ex = engine.executor
    plan = ex._resolve_subqueries(engine.plan_sql(sql))
    plan = ex._prepare(plan)
    old = ex.session.values["collect_stats"]
    ex.session.values["collect_stats"] = True
    # collect_stats changes the traced program, and is in its cache key
    try:
        t0 = time.perf_counter()
        ex.last_node_rows = {}
        ex._node_map = {}
        # the hook the distributed executor fragments through, so the
        # analyzed run measures the real (fragment-wise, mesh) shape
        ex._execute_prepared(plan)
        wall = time.perf_counter() - t0
        from presto_tpu.plan.stats import estimate_rows
        history = getattr(engine, "history", None)
        return render_analyzed(
            plan, ex._node_map, ex.last_node_rows, wall,
            ex.last_memory_estimate,
            alias=getattr(ex, "_island_alias", None),
            island_profile=getattr(ex, "last_island_profile", None),
            mesh_stats=getattr(ex, "last_mesh_stats", None),
            est=lambda n: estimate_rows(n, engine.connector, history))
    finally:
        ex.session.values["collect_stats"] = old
