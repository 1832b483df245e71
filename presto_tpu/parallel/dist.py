"""Distributed operator compositions over the mesh.

Each function here is the mesh-parallel form of a reference exchange
pattern (SURVEY.md §2.5):

  dist_aggregate      = partial agg -> hash repartition -> final agg
      (AggregationNode PARTIAL/FINAL split around a
       FIXED_HASH_DISTRIBUTION exchange, inserted by
       presto-main-base/.../sql/planner/optimizations/AddExchanges.java)
  dist_hash_join      = co-partition both sides -> local join
      (partitioned JoinNode, both children re-hashed on join keys)
  broadcast_hash_join = replicate build side -> local join
      (JoinNode distributionType=REPLICATED over BroadcastOutputBuffer)

All *_local functions run inside shard_map (axis "d"); the module-level
wrappers take stacked sharded pages plus a Mesh and jit the whole
composition. Dynamic cardinalities follow the engine-wide overflow-retry
contract: traced "needed" counters come back to the host, which re-lowers
at a bigger capacity bucket when they exceed the compiled shapes.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.data.column import Page
from presto_tpu.ops.aggregate import AggSpec, grouped_aggregate
from presto_tpu.ops.join import hash_join
from presto_tpu.parallel.mesh import AXIS, run_sharded
from presto_tpu.parallel.shuffle import (
    all_gather_page, mesh_max, partition_ids, partition_ids_cols,
    repartition_page,
)
from presto_tpu.types import BIGINT, DOUBLE


def split_agg_specs(aggs: Sequence[AggSpec], n_group: int
                    ) -> Tuple[List[AggSpec], List[AggSpec]]:
    """Rewrite SINGLE-step aggregate specs into (partial, final) pairs.

    Mirrors the planner's PARTIAL/FINAL split (reference:
    spi/plan/AggregationNode.Step + AddExchanges): the partial's output page
    is [group keys..., state columns...]; final specs index into it.
    avg carries (sum, count) state, count finalizes as sum — exactly the
    reference accumulator semantics."""
    partial: List[AggSpec] = []
    final: List[AggSpec] = []
    pos = n_group
    for a in aggs:
        if a.kind == "avg":
            partial.append(AggSpec("avg_partial", a.field, DOUBLE,
                                   mask_field=a.mask_field))
            final.append(AggSpec("avg_final", pos, a.output_type,
                                 field2=pos + 1))
            pos += 2
        elif a.kind in ("count", "count_star"):
            partial.append(AggSpec(a.kind, a.field, BIGINT,
                                   mask_field=a.mask_field))
            final.append(AggSpec("sum", pos, a.output_type))
            pos += 1
        elif a.kind == "sum128":
            # DECIMAL(38) limb lanes: partial = Decimal128 sum state,
            # final sums the limbs independently (sum128_merge)
            partial.append(AggSpec("sum128", a.field, a.output_type,
                                   mask_field=a.mask_field))
            final.append(AggSpec("sum128_merge", pos, a.output_type))
            pos += 1
        elif a.kind == "avg128":
            # exact decimal avg: (limb-lane sum, count) partial state
            partial.append(AggSpec("sum128", a.field, a.output_type,
                                   mask_field=a.mask_field))
            partial.append(AggSpec("count", a.field, BIGINT,
                                   mask_field=a.mask_field))
            final.append(AggSpec("avg128_merge", pos, a.output_type,
                                 field2=pos + 1))
            pos += 2
        elif a.kind in ("sum", "min", "max", "bool_or", "bool_and"):
            partial.append(AggSpec(a.kind, a.field, a.output_type,
                                   mask_field=a.mask_field))
            final.append(AggSpec(a.kind, pos, a.output_type))
            pos += 1
        else:
            raise NotImplementedError(f"distributed aggregate {a.kind}")
    return partial, final


def dist_aggregate_local(page: Page, group_fields: Sequence[int],
                         aggs: Sequence[AggSpec], ndev: int,
                         partial_capacity: int, out_capacity: int,
                         chunk: Optional[int] = None, axis: str = AXIS):
    """Inside-shard_map distributed aggregation. Returns
    (local final page, needed counters [partial_groups, recv, send])."""
    n_group = len(group_fields)
    partial_specs, final_specs = split_agg_specs(aggs, n_group)
    part, part_groups = grouped_aggregate(
        page, group_fields, partial_specs, partial_capacity)

    if n_group == 0:
        if ndev == 1:
            # single-device mesh: the partial IS the global state — no
            # collective, no axis_index (callable outside shard_map)
            out, _ = grouped_aggregate(part, (), final_specs, 256)
            zero = jnp.zeros((), jnp.int32)
            return out, (part_groups, zero, zero)
        # Global aggregation: single row per device; combine via all_gather
        # (tiny — the reference routes this through a SINGLE exchange) and
        # emit the result on device 0 only, honoring the disjoint-shards
        # output contract.
        gathered = all_gather_page(part, ndev, axis)
        out, _ = grouped_aggregate(gathered, (), final_specs, 256)
        on_dev0 = jnp.where(jax.lax.axis_index(axis) == 0, out.num_rows, 0)
        out = Page(out.columns, on_dev0.astype(jnp.int32), out.names)
        zero = jnp.zeros((), jnp.int32)
        return out, (part_groups, zero, zero)

    key_fields = tuple(range(n_group))
    if ndev == 1:
        # every key is already local — finalize directly; the final
        # group count stands in for total_recv so capacity annealing
        # still retries an out_capacity overflow
        out, final_groups = grouped_aggregate(
            part, key_fields, final_specs, out_capacity)
        zero = jnp.zeros((), jnp.int32)
        return out, (part_groups, final_groups, zero)
    pid = partition_ids(part, key_fields, ndev)
    recv, total_recv, max_send = repartition_page(
        part, pid, ndev, out_capacity, chunk, axis)
    out, _final_groups = grouped_aggregate(
        recv, key_fields, final_specs, out_capacity)
    # part_groups alone drives partial_capacity retries; final-side overflow
    # is covered by total_recv (recv capacity bounds final groups).
    return out, (part_groups, total_recv, max_send)


def dist_hash_join_local(probe: Page, build: Page,
                         probe_fields: Sequence[int],
                         build_fields: Sequence[int],
                         ndev: int, out_capacity: int,
                         join_type: str = "inner",
                         probe_recv_capacity: Optional[int] = None,
                         build_recv_capacity: Optional[int] = None,
                         axis: str = AXIS):
    """Co-partitioned join: rehash both sides on the join keys so equal
    keys land on the same device, then join locally. Equivalent to the
    reference's PARTITIONED join distribution."""
    if ndev == 1:
        # no repartition on a single device — join in place. The anti
        # NULL rule still applies locally (build NULL key empties the
        # output) without the cross-device pmax.
        out, pairs = hash_join(probe, build, probe_fields, build_fields,
                               out_capacity, join_type)
        if join_type in ("semi", "anti", "anti_exists"):
            out = _filter_semi_flag(out)
        if join_type == "anti":
            b_null = jnp.zeros((), bool)
            for f in build_fields:
                c = build.columns[f]
                b_null = b_null | jnp.any(c.nulls & build.row_valid())
            out = Page(out.columns,
                       jnp.where(b_null, 0,
                                 out.num_rows).astype(jnp.int32),
                       out.names)
        zero = jnp.zeros((), jnp.int32)
        return out, (pairs, zero, zero, zero, zero)
    p_cap = probe_recv_capacity or 2 * probe.capacity
    b_cap = build_recv_capacity or 2 * build.capacity
    # Keys must hash identically on both sides: string codes are only
    # comparable under a shared dictionary (ops/join._aligned_keys).
    # TODO(perf): keys are aligned+hashed again inside hash_join on the
    # recv pages; carry the 64-bit hash as an exchange column instead
    # (the reference's precomputed $hash channel,
    # HashGenerationOptimizer.java).
    from presto_tpu.ops.join import _aligned_keys
    p_key_cols, b_key_cols = _aligned_keys(probe, build, probe_fields,
                                           build_fields)
    p_pid = partition_ids_cols(p_key_cols, ndev, probe.row_valid())
    b_pid = partition_ids_cols(b_key_cols, ndev, build.row_valid())
    p_recv, p_total, p_send = repartition_page(
        probe, p_pid, ndev, p_cap, axis=axis)
    b_recv, b_total, b_send = repartition_page(
        build, b_pid, ndev, b_cap, axis=axis)
    out, pairs = hash_join(p_recv, b_recv, probe_fields, build_fields,
                           out_capacity, join_type)
    if join_type in ("semi", "anti", "anti_exists"):
        out = _filter_semi_flag(out)
    if join_type == "anti":
        # NOT IN over a partitioned build: a NULL build key lives on only
        # one device after the rehash, but makes the whole anti join empty
        # (3VL UNKNOWN). Globalize the null flag.
        b_null = jnp.zeros((), bool)
        for f in build_fields:
            c = build.columns[f]
            b_null = b_null | jnp.any(c.nulls & build.row_valid())
        b_null = jax.lax.pmax(b_null.astype(jnp.int32), axis) > 0
        out = Page(out.columns,
                   jnp.where(b_null, 0, out.num_rows).astype(jnp.int32),
                   out.names)
    return out, (pairs, p_total, p_send, b_total, b_send)


def broadcast_hash_join_local(probe: Page, build: Page,
                              probe_fields: Sequence[int],
                              build_fields: Sequence[int],
                              ndev: int, out_capacity: int,
                              join_type: str = "inner", axis: str = AXIS):
    """Replicated join: build side all_gathered to every device, probe
    stays put. The right choice when |build| << |probe| (the reference's
    REPLICATED distribution, chosen by DetermineJoinDistributionType)."""
    b_all = build if ndev == 1 else all_gather_page(build, ndev, axis)
    out, pairs = hash_join(probe, b_all, probe_fields, build_fields,
                           out_capacity, join_type)
    if join_type in ("semi", "anti", "anti_exists"):
        out = _filter_semi_flag(out)
    return out, (pairs,)


def _filter_semi_flag(out: Page) -> Page:
    """hash_join's semi/anti output is [probe cols..., match flag]; keep
    rows where the flag is set (the executor's SemiJoin lowering)."""
    from presto_tpu.data.column import compact
    flag = out.columns[-1]
    return compact(Page(out.columns[:-1], out.num_rows, out.names),
                   flag.values.astype(bool))


def gather_page_global(page: Page, ndev: int, axis: str = AXIS) -> Page:
    """Collect every device's rows into one replicated page (the root
    fragment's SINGLE-distribution gather that feeds the coordinator)."""
    if ndev == 1:
        return page
    return all_gather_page(page, ndev, axis)


# ---------------------------------------------------------------------------
# Host-level wrappers over stacked sharded pages (tests / entry points).
# ---------------------------------------------------------------------------

def _needed_max(needed) -> tuple:
    """The mesh-wide maximum of each "needed" counter, as int64 scalars
    replicated on every device (one gather for the whole tuple)."""
    m = mesh_max(jnp.stack([jnp.asarray(n, jnp.int64) for n in needed]))
    return tuple(m[i] for i in range(len(needed)))


def dist_aggregate(mesh, stacked: Page, group_fields: Sequence[int],
                   aggs: Sequence[AggSpec], partial_capacity: int,
                   out_capacity: int) -> Tuple[Page, tuple]:
    ndev = mesh.devices.size

    def fn(local: Page):
        out, needed = dist_aggregate_local(local, group_fields, aggs, ndev,
                                           partial_capacity, out_capacity)
        return out, _needed_max(needed)

    return run_sharded(mesh, fn, stacked, with_needed=True)


def dist_hash_join(mesh, probe_stacked: Page, build_stacked: Page,
                   probe_fields, build_fields, out_capacity: int,
                   join_type: str = "inner", broadcast: bool = False,
                   probe_recv_capacity: Optional[int] = None,
                   build_recv_capacity: Optional[int] = None,
                   ) -> Tuple[Page, tuple]:
    ndev = mesh.devices.size

    def fn(p: Page, b: Page):
        if broadcast:
            out, needed = broadcast_hash_join_local(
                p, b, probe_fields, build_fields, ndev, out_capacity,
                join_type)
        else:
            out, needed = dist_hash_join_local(
                p, b, probe_fields, build_fields, ndev, out_capacity,
                join_type, probe_recv_capacity, build_recv_capacity)
        return out, _needed_max(needed)

    return run_sharded(mesh, fn, probe_stacked, build_stacked,
                       with_needed=True)


def broadcast_hash_join(mesh, probe_stacked, build_stacked, probe_fields,
                        build_fields, out_capacity, join_type="inner"):
    return dist_hash_join(mesh, probe_stacked, build_stacked, probe_fields,
                          build_fields, out_capacity, join_type,
                          broadcast=True)
