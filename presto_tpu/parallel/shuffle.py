"""ICI shuffle primitives — run INSIDE shard_map over axis "d".

The TPU-native form of Presto's partitioned exchange (SURVEY.md §3.5):

  PartitionedOutputOperator.addInput      -> partition_ids + pack_by_partition
    (presto-main-base/.../operator/repartition/PartitionedOutputOperator.java:57,
     hash via InterpretedHashGenerator)
  PagesSerde + HTTP pull + ExchangeClient -> lax.all_to_all over ICI
    (.../operator/ExchangeClient.java:71)
  BroadcastOutputBuffer                   -> lax.all_gather
    (.../execution/buffer/BroadcastOutputBuffer.java)

Static-shape contract: each device sends at most `chunk` rows to each peer
(chunk is a compile-time constant). Skew beyond the chunk, or receive totals
beyond out_capacity, are reported back as traced "needed" counters so the
host can re-lower at a bigger bucket — the same overflow-retry protocol the
local operators use (exec/executor.py).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu.data.column import Column, Page
from presto_tpu.ops.keys import hash_columns
from presto_tpu.parallel.mesh import AXIS


def mesh_max(x: jnp.ndarray, axis: str = AXIS) -> jnp.ndarray:
    """Elementwise maximum of `x` over the mesh axis, replicated on every
    device. Gather-then-max, not `lax.pmax`: for 64-bit integers the TPU
    compiler lowers only Sum all-reduces, while a 64-bit all_gather
    lowers. Callers reduce a handful of counters, so the gather moves a
    few bytes."""
    return jnp.max(jax.lax.all_gather(x, axis), axis=0)


def partition_ids(page: Page, key_fields: Sequence[int], ndev: int
                  ) -> jnp.ndarray:
    """Hash-partition id per row in [0, ndev); padding rows get ndev.
    NULL keys hash to a stable bin (null==null for partitioning, matching
    the reference's hash-partitioning of nullable group keys)."""
    return partition_ids_cols([page.columns[f] for f in key_fields],
                              ndev, page.row_valid())


def partition_ids_cols(cols: Sequence[Column], ndev: int,
                       valid: jnp.ndarray) -> jnp.ndarray:
    """partition_ids over explicit key columns (already cross-side aligned
    for joins — string codes only hash consistently across pages when the
    columns share one dictionary, cf. ops/join._aligned_keys)."""
    h = hash_columns(cols)
    pid = (h % ndev).astype(jnp.int32)
    return jnp.where(valid, pid, ndev)


class ExchangeLayout:
    """Host-visible description of one packed exchange, recorded at trace
    time (shapes/dtypes are static): how many collectives the exchange
    launches (one per distinct lane dtype) and the static wire-buffer
    bytes it moves across the mesh per execution. Feeds the mesh metrics
    (obs) without touching the traced values."""

    __slots__ = ("kind", "collectives", "wire_bytes")

    def __init__(self, kind: str, collectives: int, wire_bytes: int):
        self.kind = kind
        self.collectives = collectives
        self.wire_bytes = wire_bytes


def _packed_all_to_all(parts, axis: str, ndev: int, sink=None):
    """One `lax.all_to_all` per distinct dtype: same-dtype [ndev, w] blocks
    are concatenated along axis 1, exchanged in a single collective, and
    sliced back apart. Collapsing the per-lane collectives into per-dtype
    ones is what keeps the ICI launch count independent of column count.
    Returns outputs in input order."""
    groups = {}
    for i, p in enumerate(parts):
        groups.setdefault(jnp.dtype(p.dtype), []).append(i)
    if sink is not None:
        wire = ndev * sum(int(p.size) * p.dtype.itemsize for p in parts)
        sink(ExchangeLayout("repartition", len(groups), wire))
    out = [None] * len(parts)
    for idxs in groups.values():
        stacked = (parts[idxs[0]] if len(idxs) == 1 else
                   jnp.concatenate([parts[i] for i in idxs], axis=1))
        ex = jax.lax.all_to_all(stacked, axis, split_axis=0, concat_axis=0)
        off = 0
        for i in idxs:
            w = parts[i].shape[1]
            out[i] = ex[:, off:off + w]
            off += w
    return out


def _packed_all_gather(parts, axis: str, ndev: int, sink=None):
    """One `lax.all_gather` per distinct dtype: same-dtype 1-D [w] blocks
    are concatenated, gathered once into [ndev, sum(w)], and sliced back.
    Returns [ndev, w] outputs in input order."""
    groups = {}
    for i, p in enumerate(parts):
        groups.setdefault(jnp.dtype(p.dtype), []).append(i)
    if sink is not None:
        wire = ndev * ndev * sum(
            int(p.size) * p.dtype.itemsize for p in parts)
        sink(ExchangeLayout("broadcast", len(groups), wire))
    out = [None] * len(parts)
    for idxs in groups.values():
        stacked = (parts[idxs[0]] if len(idxs) == 1 else
                   jnp.concatenate([parts[i] for i in idxs]))
        g = jax.lax.all_gather(stacked, axis)
        off = 0
        for i in idxs:
            w = parts[i].shape[0]
            out[i] = g[:, off:off + w]
            off += w
    return out


def _pack_by_partition(arrs, pid, ndev: int, chunk: int, valid):
    """Scatter rows into per-destination blocks.

    Returns (packed arrays shaped [ndev, chunk], counts [ndev], max_count).
    Rows beyond `chunk` for a destination are dropped (reported via
    max_count so the host retries)."""
    cap = pid.shape[0]
    order = jnp.argsort(pid, stable=True)          # group rows by dest
    spid = pid[order]
    idx = jnp.arange(cap, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), spid[1:] != spid[:-1]])
    from presto_tpu.ops.scan import blocked_cummax
    seg_start = blocked_cummax(jnp.where(is_start, idx, 0))
    rank = idx - seg_start
    counts = jnp.zeros((ndev + 1,), jnp.int32).at[spid].add(
        valid[order].astype(jnp.int32))[:ndev]
    ok = (rank < chunk) & (spid < ndev) & valid[order]
    slot = jnp.where(ok, spid * chunk + rank, ndev * chunk)
    packed = []
    for a in arrs:
        buf = jnp.zeros((ndev * chunk + 1,), dtype=a.dtype)
        buf = buf.at[slot].set(a[order], mode="drop")
        packed.append(buf[:ndev * chunk].reshape(ndev, chunk))
    return packed, counts, jnp.max(counts)


def repartition_page(page: Page, pid: jnp.ndarray, ndev: int,
                     out_capacity: int, chunk: Optional[int] = None,
                     axis: str = AXIS, layout_sink=None
                     ) -> Tuple[Page, jnp.ndarray, jnp.ndarray]:
    """All-to-all exchange: each row moves to device pid[row].

    Must run inside shard_map over `axis`. Returns
    (local page of received rows with capacity out_capacity,
     needed_recv  — true received total (may exceed out_capacity),
     needed_send  — max rows destined to one peer (may exceed chunk)).

    All lanes of the page ride a single all_to_all per distinct dtype
    (the per-peer counts travel in the int32 group), so launch count is
    bounded by the number of dtypes, not the number of columns.
    `layout_sink`, if given, is called at trace time with the
    ExchangeLayout describing the packed collectives.
    """
    cap = page.capacity
    if chunk is None:
        chunk = max(2 * cap // ndev, 64)
    valid = page.row_valid()

    arrs = []
    lane_counts = []
    for c in page.columns:
        lanes = _col_lanes(c)
        lane_counts.append(len(lanes))
        arrs.extend(lanes)
    packed, counts, max_send = _pack_by_partition(
        arrs, pid, ndev, chunk, valid)

    # counts[d] = rows we send to d; exchange so recv_counts[j] = rows
    # device j sent to me. The [ndev, 1] counts block packs into the
    # int32 dtype group alongside any int32 column lanes.
    exchanged = _packed_all_to_all(
        [counts.reshape(ndev, 1)] + packed, axis, ndev, sink=layout_sink)
    recv_counts = exchanged[0].reshape(ndev)
    recv = exchanged[1:]

    # Flatten [ndev, chunk] -> [ndev*chunk]; block j's first
    # min(recv_counts[j], chunk) rows are live.
    row_in_block = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    live = (row_in_block < jnp.minimum(recv_counts, chunk)[:, None]
            ).reshape(ndev * chunk)
    total = jnp.sum(recv_counts)

    flat = []
    pos = 0
    for c, nl in zip(page.columns, lane_counts):
        flat.append(([r.reshape(ndev * chunk)
                      for r in recv[pos:pos + nl]], c))
        pos += nl
    out = _compact_flat(flat, live, out_capacity, page.names)
    return out, total, max_send


def _col_lanes(c):
    """A column's row-wise device lanes (Decimal128 = hi/lo/nulls[/cnt],
    plain = values/nulls) — the unit the all-to-all exchange moves."""
    from presto_tpu.data.column import Decimal128Column
    if isinstance(c, Decimal128Column):
        return list(c.row_lanes())
    return [c.values, c.nulls]


def _compact_flat(flat_cols, live: jnp.ndarray, out_capacity: int,
                  names) -> Page:
    """Stable-partition live rows to the front of an out_capacity page.
    flat_cols: [(lane arrays, template Column)] with 1-D arrays."""
    from presto_tpu.data.column import Decimal128Column

    flat_cap = live.shape[0]
    order_key = jnp.where(live, 0, flat_cap) + jnp.arange(
        flat_cap, dtype=jnp.int32)
    perm = jnp.argsort(order_key)
    n = jnp.sum(live).astype(jnp.int32)
    take = jnp.arange(out_capacity, dtype=jnp.int32)
    src = perm[jnp.clip(take, 0, flat_cap - 1)]
    out_valid = take < jnp.minimum(n, out_capacity)

    cols = []
    for lanes, c in flat_cols:
        if isinstance(c, Decimal128Column):
            g = Decimal128Column.mask_lanes(
                [lane[src] for lane in lanes], out_valid)
            cols.append(c.from_lanes(g))
            continue
        vals, nulls = lanes
        v = vals[src]
        nl = nulls[src]
        sent = jnp.asarray(c.type.null_sentinel(), dtype=v.dtype)
        v = jnp.where(out_valid, v, sent)
        nl = jnp.where(out_valid, nl, True)
        cols.append(Column(v, nl, c.type, c.dictionary))
    return Page(tuple(cols), jnp.minimum(n, out_capacity), names)


def range_partition_ids(page: Page, sort_key, ndev: int,
                        samples_per_dev: int = 256,
                        axis: str = AXIS) -> jnp.ndarray:
    """Partition ids for a sampled range partition on the FIRST sort key:
    device d receives the d-th key range, so local sorts compose into a
    global order by device index (the distributed-sort exchange;
    reference role: MergeOperator's ordered exchange + benchto
    distributed_sort.yaml). Rows with equal keys always map to one
    device, so ties never straddle a boundary. Must run inside shard_map.

    Keys are reduced to a monotone f64 rank (nulls/direction folded in):
    monotonicity is all correctness needs — rounding only shifts split
    boundaries, never reorders."""
    from presto_tpu.ops.keys import _orderable_values

    col = page.columns[sort_key.field]
    v = _orderable_values(col).astype(jnp.float64)
    if not sort_key.ascending:
        v = -v
    null_v = jnp.float64(-jnp.inf if sort_key.nulls_sort_first else jnp.inf)
    v = jnp.where(col.nulls, null_v, v)
    valid = page.row_valid()

    cap = page.capacity
    stride = max(cap // samples_per_dev, 1)
    sample_idx = jnp.arange(samples_per_dev, dtype=jnp.int32) * stride
    sample_idx = jnp.clip(sample_idx, 0, cap - 1)
    s_vals = jnp.take(v, sample_idx, mode="clip")
    s_ok = jnp.take(valid, sample_idx, mode="clip")
    s_vals = jnp.where(s_ok, s_vals, jnp.inf)      # invalid samples last

    all_vals = jax.lax.all_gather(s_vals, axis).reshape(-1)
    all_ok = jax.lax.all_gather(s_ok, axis).reshape(-1)
    n_samples = all_vals.shape[0]
    sorted_vals = jax.lax.sort(all_vals)
    n_ok = jnp.sum(all_ok)
    # ndev-1 splitters at sample quantiles of the valid prefix
    q = (jnp.arange(1, ndev, dtype=jnp.int32)
         * jnp.maximum(n_ok, 1)) // ndev
    splitters = jnp.take(sorted_vals,
                         jnp.clip(q, 0, n_samples - 1), mode="clip")
    pid = jnp.zeros((cap,), jnp.int32)
    for i in range(ndev - 1):
        pid = pid + (v >= splitters[i]).astype(jnp.int32)
    return jnp.where(valid, pid, ndev)


def all_gather_page(page: Page, ndev: int, axis: str = AXIS,
                    layout_sink=None) -> Page:
    """Replicate all rows of a sharded page onto every device (broadcast
    build side of a join). Output capacity is ndev * local capacity, rows
    compacted to the front. Must run inside shard_map over `axis`.

    Like repartition_page, all lanes travel in one all_gather per
    distinct dtype; the per-device row counts pack into the int32 group.
    """
    cap = page.capacity
    flat_cap = ndev * cap

    arrs = [jnp.reshape(page.num_rows, (1,)).astype(jnp.int32)]
    lane_counts = []
    for c in page.columns:
        lanes = _col_lanes(c)
        lane_counts.append(len(lanes))
        arrs.extend(lanes)
    gathered = _packed_all_gather(arrs, axis, ndev, sink=layout_sink)
    nums = gathered[0].reshape(ndev)                      # [ndev]
    live = (jnp.arange(cap, dtype=jnp.int32)[None, :]
            < nums[:, None]).reshape(flat_cap)

    flat = []
    pos = 1
    for c, nl in zip(page.columns, lane_counts):
        flat.append(([g.reshape(flat_cap)
                      for g in gathered[pos:pos + nl]], c))
        pos += nl
    return _compact_flat(flat, live, flat_cap, page.names)
