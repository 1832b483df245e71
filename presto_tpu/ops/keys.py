"""Key normalization, multi-key sort permutations, and vectorized hashing.

These are the shared primitives under grouping, joins, sorting and the
partitioned exchange — the roles the reference implements with
MultiChannelGroupByHash (presto-main-base/.../operator/MultiChannelGroupByHash.java:55),
PagesIndex sorting (.../operator/PagesIndex.java) and
InterpretedHashGenerator (.../operator/InterpretedHashGenerator.java).
TPU-first design: everything is a statically-shaped argsort / gather /
bit-mix — no open-addressing probe loops on device.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp

from presto_tpu.data.column import Column, Page


@dataclasses.dataclass(frozen=True)
class SortKey:
    field: int
    ascending: bool = True
    # Presto default: nulls are "larger than any value" — last for ASC,
    # first for DESC (reference: presto-common/.../SortOrder.java).
    nulls_first: Optional[bool] = None

    @property
    def nulls_sort_first(self) -> bool:
        if self.nulls_first is not None:
            return self.nulls_first
        return not self.ascending


def _orderable_values(col: Column) -> jnp.ndarray:
    """Per-type array whose ascending order == SQL ascending order.
    Strings are already codes into a sorted dictionary. Decimal128
    columns order by their float64 image — exact to 2^53; ORDER BY
    uses `_orderable_lanes` instead for exact 128-bit ordering."""
    from presto_tpu.data.column import Decimal128Column
    if isinstance(col, Decimal128Column):
        img = (col.l3.astype(jnp.float64) * float(2 ** 96)
               + col.l2.astype(jnp.float64) * float(2 ** 64)
               + col.l1.astype(jnp.float64) * float(2 ** 32)
               + col.l0.astype(jnp.float64))
        if col.count is not None:
            img = img / jnp.maximum(col.count, 1).astype(jnp.float64)
        return img
    v = col.values
    if v.dtype == jnp.bool_:
        return v.astype(jnp.int32)
    return v


def _orderable_lanes(col: Column):
    """Sort-key lanes, most-significant first; lexicographic comparison
    of the lanes == SQL ascending order. Decimal128 values/SUMS sort
    exactly: normalize carries up the four limb lanes (l2/l1/l0
    accumulate unsigned 32-bit limbs, so each lane's overflow carries
    into the next), then (l3, l2, l1, l0) lexicographic IS value order
    because the lower lanes land in [0, 2^32) and l3 keeps the sign.
    Averages (count set) keep the float64 image of sum/count — a ratio
    has no per-row sort key that is exact without division."""
    from presto_tpu.data.column import Decimal128Column
    if isinstance(col, Decimal128Column) and col.count is None:
        m = jnp.int64(0xFFFFFFFF)
        t0 = col.l0
        n0 = t0 & m
        t1 = col.l1 + (t0 >> 32)
        n1 = t1 & m
        t2 = col.l2 + (t1 >> 32)
        n2 = t2 & m
        t3 = col.l3 + (t2 >> 32)
        return [t3, n2, n1, n0]
    return [_orderable_values(col)]


def group_values(col: Column) -> jnp.ndarray:
    """Per-type array where equality/order == SQL group equality/order.
    Floats stay raw f64 — NO canonicalization and NO 64-bit bitcasts
    (the TPU backend's X64-rewriting pass cannot lower bitcast-convert
    on 64-bit element types in either direction). Float keys sort and
    compare as floats: XLA's sort is total-order with every NaN last,
    IEEE == already treats -0.0 == +0.0, and equality sites must use
    `values_equal` for NaN == NaN; `f64_hash_lanes` collapses NaN/zero
    classes itself for hashing."""
    v = col.values
    if v.dtype == jnp.float64 or v.dtype == jnp.float32:
        # no bit-canonicalization needed: -0.0 == 0.0 under IEEE ==,
        # values_equal handles NaN == NaN, and f64_hash_lanes collapses
        # every NaN/zero to one hash itself
        return v.astype(jnp.float64)
    if v.dtype == jnp.bool_:
        return v.astype(jnp.int64)
    return v.astype(jnp.int64)


def values_equal(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Group-key equality over group_values outputs: NaN == NaN (SQL
    grouping semantics). `x != x` is False for every non-float dtype, so
    this is a no-op for ints."""
    return (a == b) | ((a != a) & (b != b))


def f64_hash_lanes(v: jnp.ndarray) -> jnp.ndarray:
    """Deterministic u64 hash input for f64 values without bitcasting:
    SCALE-AWARE exponent + top-32-mantissa-bit lanes extracted
    arithmetically (log2/exp2), so entropy survives at every magnitude
    (a fixed-point trunc/frac split would collapse everything below
    2^-32 absolute). Values equal to ~32 significant bits collide —
    callers use it for bucketing/partitioning only, never equality."""
    is_nan = jnp.isnan(v)
    is_inf = jnp.isinf(v)
    safe = jnp.where(is_nan | is_inf, 1.0, v)
    ae = jnp.maximum(jnp.abs(safe), 1e-300)
    # floor(log2): ±1 ulp of log2 can misplace the boundary by one —
    # that only shifts which 32 mantissa bits we sample, still distinct
    e = jnp.floor(jnp.log2(ae))
    norm = ae * jnp.exp2(-e)                       # ~[1, 2)
    mant = (jnp.clip(norm - 1.0, 0.0, 1.0)
            * (2.0 ** 32)).astype(jnp.uint64)
    eb = (e.astype(jnp.int64) + 2048).astype(jnp.uint64)
    h = eb * _GOLDEN ^ mant
    h = jnp.where(v < 0, h ^ jnp.uint64(0xA5A5A5A5DEADBEEF), h)
    h = jnp.where(v == 0.0, jnp.uint64(0x5E5E0000), h)   # ±0 hash equal
    h = jnp.where(is_nan, jnp.uint64(0x7FF8000000000001), h)
    h = jnp.where(is_inf & (v > 0), jnp.uint64(0x7FF0000000000000), h)
    h = jnp.where(is_inf & (v < 0), jnp.uint64(0xFFF0000000000000), h)
    return h


def lex_perm(lanes: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Permutation sorting rows lexicographically by `lanes` (most
    significant first, each ascending), via composed STABLE argsorts —
    2-operand sorts only. On this stack a wide variadic lax.sort's
    compile cost explodes with operand count (20 operands at SF1 shapes
    never finished compiling; PR 27 measured ~40 s for two operands and
    ~25 s for each one more, twice that when stable, for a described
    v5e), while a gather compiles in under a second; every operator but
    merge_join therefore sorts via this helper and gathers its payload by
    the permutation. The gathers are NOT free on the chip: a 1-D gather
    takes 7-9 ns an element for every 32-bit lane, ~20 ns where the table
    is out of near memory (50-62 ms for 2-3 M elements; ledger, PR 26),
    and each `lane[perm]` and `perm[...]` here is one, 64-bit wide under
    x64 (argsort's iota is int64). ops/join.merge_join shows the way
    round them: a tag as last key, and the lanes riding the sort."""
    perm = None
    for lane in reversed(list(lanes)):
        if perm is None:
            perm = jnp.argsort(lane, stable=True)
        else:
            perm = perm[jnp.argsort(lane[perm], stable=True)]
    return perm


def sort_perm(page: Page, keys: Sequence[SortKey]) -> jnp.ndarray:
    """Permutation that stably sorts valid rows by `keys` with SQL null
    ordering; padding rows always sort last. Implemented as composed stable
    argsorts, least-significant key first."""
    cap = page.capacity
    perm = jnp.arange(cap, dtype=jnp.int32)
    for k in reversed(list(keys)):
        col = page.columns[k.field]
        # Multi-lane keys (Decimal128): least-significant lane first,
        # each pass a stable argsort, composing to lexicographic order.
        for lane in reversed(_orderable_lanes(col)):
            v = lane[perm]
            if not k.ascending:
                # Descending: sort on rank under reversed order. Negate
                # where safe; codes/limbs negate fine in int64.
                v = -v.astype(jnp.int64) if v.dtype != jnp.float64 \
                    and v.dtype != jnp.float32 else -v
            perm = perm[jnp.argsort(v, stable=True)]
        # Null placement: stable two-pass — values first, then null bucket.
        n = col.nulls[perm]
        null_key = jnp.where(n, 0, 1) if k.nulls_sort_first else \
            n.astype(jnp.int32)
        perm = perm[jnp.argsort(null_key, stable=True)]
    # Padding rows last (most-significant).
    pad = (jnp.arange(cap, dtype=jnp.int32) >= page.num_rows)[perm]
    perm = perm[jnp.argsort(pad.astype(jnp.int32), stable=True)]
    return perm


def new_group_flags(page: Page, fields: Sequence[int],
                    perm: jnp.ndarray) -> jnp.ndarray:
    """After sorting by `fields`, True where a row starts a new group
    (row 0 is always a start). Null == null for grouping."""
    cap = page.capacity
    flags = jnp.zeros((cap,), dtype=bool).at[0].set(True)
    for f in fields:
        col = page.columns[f]
        v = group_values(col)[perm]
        n = col.nulls[perm]
        prev_v = jnp.roll(v, 1)
        prev_n = jnp.roll(n, 1)
        same = (values_equal(v, prev_v) & ~n & ~prev_n) | (n & prev_n)
        flags = flags | ~same
    return flags.at[0].set(True)


# -- hashing ---------------------------------------------------------------

_SPLITMIX_C1 = jnp.uint64(0xBF58476D1CE4E5B9)
_SPLITMIX_C2 = jnp.uint64(0x94D049BB133111EB)
_GOLDEN = jnp.uint64(0x9E3779B97F4A7C15)


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    x = (x ^ (x >> jnp.uint64(30))) * _SPLITMIX_C1
    x = (x ^ (x >> jnp.uint64(27))) * _SPLITMIX_C2
    return x ^ (x >> jnp.uint64(31))


def hash_columns(cols: Sequence[Column]) -> jnp.ndarray:
    """Combined 64-bit hash of the key columns per row (splitmix64 mixing).
    NULL hashes to a fixed tag so null==null grouping/partitioning works;
    join ops must still exclude null keys explicitly (SQL: null != null).

    The reference role: InterpretedHashGenerator / HashGenerationOptimizer's
    precomputed $hash channel."""
    h = jnp.zeros((cols[0].capacity,), dtype=jnp.uint64)
    for c in cols:
        g = group_values(c)
        if jnp.issubdtype(g.dtype, jnp.floating):
            v = f64_hash_lanes(g)     # arithmetic lanes, no bitcast
        else:
            v = g.astype(jnp.uint64)
        v = jnp.where(c.nulls, jnp.uint64(0x5BD1E995), v)
        h = _mix64(h ^ (v + _GOLDEN + (h << jnp.uint64(6))
                        + (h >> jnp.uint64(2))))
    return h.astype(jnp.int64) & jnp.int64(0x7FFFFFFFFFFFFFFF)
