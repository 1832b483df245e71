"""Grouped aggregation — sort-based, fully vectorized.

The engine's analogue of HashAggregationOperator
(presto-main-base/.../operator/HashAggregationOperator.java:56,413 over
MultiChannelGroupByHash.java:55). TPU-first redesign: instead of an
open-addressing hash table probed row-at-a-time, we sort by the group keys
(one fused multi-key argsort), detect group boundaries, and reduce with
segment ops — every step is a statically-shaped XLA op that maps onto the
vector units; no data-dependent control flow.

Partial/final split (the distributed pattern, reference
AggregationNode.Step): `grouped_aggregate` evaluates any step; AVG carries
(sum, count) through partials exactly like the reference's accumulator
states.

Capacity contract: the output page has static capacity `out_capacity`
(default: input capacity). If the true group count exceeds it, num_rows is
clamped and `overflowed(page)` lets the host re-run at a bigger bucket —
the engine's recompile-and-retry answer to dynamic cardinalities
(SURVEY.md §7.3 #1).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp

from presto_tpu.data.column import Column, Page
from presto_tpu.types import BIGINT, DOUBLE, Type


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate: kind in {sum,count,count_star,min,max,avg,
    sum_partial,count_partial,avg_partial,avg_final,...}.

    Step handling (mirrors AggregationNode.Step PARTIAL/FINAL/SINGLE):
      - SINGLE: kind as-is over raw input.
      - PARTIAL: avg -> emits two columns (sum, count); others emit their
        partial state (sum/count/min/max).
      - FINAL: count -> sum of partial counts; avg -> sum(sums)/sum(counts).
    The *planner* rewrites kinds for partial/final; this op just evaluates
    what it is given.
    """
    kind: str
    field: Optional[int]          # input column (None for count_star)
    output_type: Type
    field2: Optional[int] = None  # second state input (avg_final: count)
    mask_field: Optional[int] = None  # FILTER / mask channel (bool column)
    param: Optional[float] = None  # extra literal (approx_percentile p)


# ---------------------------------------------------------------------------
# HyperLogLog pieces (approx_distinct)
#
# Reference: operator/aggregation/ApproximateCountDistinctAggregation +
# airlift HLL (dense). TPU-first shape: never a per-row register-table
# scatter — registers materialize as (register, rank) pairs carried through
# the SAME multi-operand sorts the rest of the aggregation uses; the max
# rank per register is whoever sorts first in its (group, register) run.
# Default precision matches Presto's 2.3% standard error tier.
# ---------------------------------------------------------------------------

_HLL_P = 11
_HLL_M = 1 << _HLL_P
_HLL_ALPHA = 0.7213 / (1.0 + 1.079 / _HLL_M)


def _hll_reg_rank(vals: jnp.ndarray):
    """Per-row (register id int32, rank int32). rank = leading-zero count
    of the hash's top 64-p bits, + 1."""
    import jax

    from presto_tpu.ops.keys import _GOLDEN, _mix64

    if jnp.issubdtype(vals.dtype, jnp.floating):
        # scale-aware arithmetic lanes (no 64-bit bitcasts on TPU);
        # values equal to ~32 significant bits collide, slightly
        # undercounting only when a column has >2^32-fine distinctions
        from presto_tpu.ops.keys import f64_hash_lanes
        bits = f64_hash_lanes(vals.astype(jnp.float64))
    else:
        bits = vals.astype(jnp.uint64)
    h = _mix64(bits + _GOLDEN)
    reg = (h & jnp.uint64(_HLL_M - 1)).astype(jnp.int32)
    w = h >> jnp.uint64(_HLL_P)
    # floor(log2(w)) via f32 frexp — f64 frexp would need a 64-bit
    # bitcast, which the TPU X64-rewriting pass cannot lower. The f32
    # rounding can bump w across a power of two for ~2^-24 of values,
    # nudging one rank — noise far below the sketch's 2.3% error.
    _mant, exp = jnp.frexp(w.astype(jnp.float32))
    rank = jnp.where(w == 0, 64 - _HLL_P + 1,
                     (64 - _HLL_P) - (exp - 1)).astype(jnp.int32)
    return reg, rank


def _hll_estimate(present_sum: jnp.ndarray, zeros: jnp.ndarray):
    """Registers -> cardinality: raw harmonic-mean estimate with the
    standard linear-counting small-range correction."""
    m = float(_HLL_M)
    zeros_f = zeros.astype(jnp.float64)
    raw = _HLL_ALPHA * m * m / jnp.maximum(
        present_sum + zeros_f, 1e-12)
    small = m * jnp.log(m / jnp.maximum(zeros_f, 1.0))
    use_small = (raw <= 2.5 * m) & (zeros > 0)
    return jnp.where(use_small, small, raw)


# Direct (sort-free, scatter-free) grouping.
#
# When every group key has a small static domain (dictionary-coded strings,
# booleans) the group id is a mixed-radix code computed per row, and each
# aggregate becomes a handful of masked whole-array reductions — one per
# bin — instead of a per-row scatter-add. On TPU this matters twice over:
# no argsorts (the general path's grouping mechanism) and no scatters
# (which XLA serializes row-by-row for colliding indices: measured ~0.65 s
# per scatter over 8M rows vs ~0.06 s for the fused masked reductions).
# The TPU counterpart of MultiChannelGroupByHash's dictionary fast path.

_DIRECT_MAX_BINS = 64


def _direct_domains(page: Page, group_fields: Sequence[int]):
    """Per-key domain sizes if the direct path applies, else None."""
    domains = []
    for f in group_fields:
        c = page.columns[f]
        if c.type.is_string and c.dictionary is not None:
            domains.append(len(c.dictionary))
        elif c.values.dtype == jnp.bool_:
            domains.append(2)
        else:
            return None
    prod = 1
    for d in domains:
        prod *= d + 1                      # +1: per-key NULL bin
        if prod > _DIRECT_MAX_BINS:
            return None
    return domains, prod


def _direct_grouped_aggregate(page: Page, group_fields: Sequence[int],
                              aggs: Sequence[AggSpec], out_cap: int,
                              valid: jnp.ndarray, domains, prod: int,
                              min_groups: int = 0):
    cap = page.capacity
    code = jnp.zeros((cap,), jnp.int32)
    for f, dom in zip(group_fields, domains):
        c = page.columns[f]
        v = jnp.clip(c.values.astype(jnp.int32), 0, dom - 1)
        v = jnp.where(c.nulls, dom, v)     # NULL sorts after all codes
        code = code * (dom + 1) + v

    # Per-bin row masks; XLA fuses these into a few passes over the page.
    masks = [valid & (code == b) for b in range(prod)]
    counts = jnp.stack([jnp.sum(m) for m in masks])          # [prod]
    nonempty = counts > 0
    num_groups = jnp.maximum(jnp.sum(nonempty), min_groups).astype(jnp.int32)

    # Compact non-empty bins to the front; raw bin order == sorted key
    # order (sorted dictionaries), nulls last per key.
    order_key = jnp.where(nonempty, 0, prod) + jnp.arange(prod,
                                                          dtype=jnp.int32)
    bin_perm = jnp.argsort(order_key)                        # [prod] tiny
    width = min(out_cap, prod)
    take = bin_perm[:width]
    out_valid_w = jnp.arange(width, dtype=jnp.int32) < num_groups

    def widen(bins_arr, t: Type, nulls_w, dictionary=None):
        """Place per-bin results [prod] into an out_cap column."""
        v = bins_arr[take].astype(t.dtype)
        sent = jnp.asarray(t.null_sentinel(), dtype=t.dtype)
        v = jnp.where(nulls_w | ~out_valid_w, sent, v)
        nl = nulls_w | ~out_valid_w
        if width < out_cap:
            pad = out_cap - width
            v = jnp.concatenate([v, jnp.full((pad,), sent, dtype=t.dtype)])
            nl = jnp.concatenate([nl, jnp.ones((pad,), bool)])
        return Column(v, nl, t, dictionary)

    cols = []
    # Group keys: decode the mixed-radix bin index statically.
    stride = prod
    for f, dom in zip(group_fields, domains):
        c = page.columns[f]
        stride //= (dom + 1)
        key_code = (jnp.arange(prod, dtype=jnp.int32) // stride) % (dom + 1)
        knull = key_code == dom
        cols.append(widen(jnp.where(knull, 0, key_code), c.type,
                          knull[take], c.dictionary))

    false_w = jnp.zeros((width,), bool)
    for a in aggs:
        if a.kind in ("sum128_merge", "avg128_merge"):
            # FINAL over Decimal128 partial states (global/no-GROUP-BY
            # distributed DECIMAL aggregation routes here): sum the
            # limb lanes independently per bin.
            from presto_tpu.data.column import Decimal128Column
            pc = page.columns[a.field]
            live_m = [m & ~pc.nulls for m in masks]
            n_per = jnp.stack([jnp.sum(lv) for lv in live_m])
            lane_b = [jnp.stack([jnp.sum(jnp.where(lv, lane, 0))
                                 for lv in live_m])
                      for lane in pc.value_lanes]
            count_b = None
            if a.kind == "avg128_merge":
                cc = page.columns[a.field2]
                cl = [m & ~cc.nulls for m in masks]
                count_b = jnp.stack(
                    [jnp.sum(jnp.where(lv2, cc.values, 0))
                     for lv2 in cl]).astype(jnp.int64)
            is_null = (n_per == 0)[take] | ~out_valid_w

            def lane128(bins_arr, fill=0):
                v = jnp.where(is_null, fill, bins_arr[take])
                if width < out_cap:
                    v = jnp.concatenate(
                        [v, jnp.full((out_cap - width,), fill,
                                     dtype=v.dtype)])
                return v
            nl = is_null
            if width < out_cap:
                nl = jnp.concatenate(
                    [nl, jnp.ones((out_cap - width,), bool)])
            cols.append(Decimal128Column(
                *[lane128(b) for b in lane_b], nl, a.output_type,
                count=(lane128(count_b) if count_b is not None
                       else None)))
            continue
        vals, nulls = _agg_inputs(a, page)
        dictionary = (page.columns[a.field].dictionary
                      if a.field is not None and a.output_type.is_string
                      else None)
        t = a.output_type
        kind = a.kind
        if (a.field is not None
                and not hasattr(page.columns[a.field], "values")
                and kind not in ("sum128", "avg128", "count",
                                 "min", "max")):
            # vals is only the l0 limb for wide inputs — anything that
            # would consume it as a value must reject, not mis-compute
            raise NotImplementedError(f"{kind} over DECIMAL(38) input")
        live = [m & ~nulls for m in masks]
        n_per = jnp.stack([jnp.sum(lv) for lv in live])
        if kind == "count_star":
            cols.append(widen(counts.astype(jnp.int64), t, false_w))
        elif kind == "count":
            cols.append(widen(n_per.astype(jnp.int64), t, false_w))
        elif kind in ("sum", "avg", "avg_partial", "avg_final"):
            acc = jnp.float64 if (t.is_floating or kind != "sum") \
                else jnp.int64
            zero = jnp.asarray(0, dtype=acc)
            s = jnp.stack([jnp.sum(jnp.where(lv, vals, zero).astype(acc))
                           for lv in live])
            if acc == jnp.int64:
                # checked SUM (BigintOperators-style): an int64 total that
                # wrapped is ~2^64 away from the float64 shadow sum, far
                # beyond float rounding error (~n * 2^11 at n=10^7)
                from presto_tpu.expr import errors as E
                fs = jnp.stack([jnp.sum(
                    jnp.where(lv, vals, zero).astype(jnp.float64))
                    for lv in live])
                code = E.OVF_DECIMAL if t.is_decimal else E.OVF_SUM
                E.record(code, jnp.any(
                    jnp.abs(fs - s.astype(jnp.float64)) > 2.0 ** 62))
            if kind == "avg_final":
                c2 = page.columns[a.field2]
                c2v = jnp.where(c2.nulls, 0, c2.values)
                n2 = jnp.stack([jnp.sum(jnp.where(m, c2v, 0))
                                for m in masks])
                cols.append(widen(s / jnp.maximum(n2, 1), t,
                                  (n2 == 0)[take]))
            elif kind == "sum":
                cols.append(widen(s, t, (n_per == 0)[take]))
            elif kind == "avg":
                cols.append(widen(s / jnp.maximum(n_per, 1), t,
                                  (n_per == 0)[take]))
            else:  # avg_partial -> (sum double, count bigint)
                cols.append(widen(s, DOUBLE, (n_per == 0)[take]))
                cols.append(widen(n_per.astype(jnp.int64), BIGINT, false_w))
        elif kind in ("sum128", "avg128"):
            # DECIMAL(38): four 32-bit limb sums per bin (int64 inputs
            # decompose device-side; wide inputs already carry lanes);
            # exact recombination happens host-side
            # (Decimal128Column.value_at)
            from presto_tpu.data.column import Decimal128Column
            pc = page.columns[a.field]
            in_lanes = (pc.value_lanes if isinstance(pc, Decimal128Column)
                        else Decimal128Column.decompose_int64(vals))
            live_s = jnp.stack(live)
            lane_b = [jnp.sum(jnp.where(live_s, x.astype(jnp.int64), 0),
                              axis=1) for x in in_lanes]
            nulls_w = (n_per == 0)[take]
            is_null = nulls_w | ~out_valid_w

            def lane(bins_arr, fill=0):
                v = jnp.where(is_null, fill, bins_arr[take])
                if width < out_cap:
                    pad = out_cap - width
                    v = jnp.concatenate(
                        [v, jnp.full((pad,), fill, dtype=v.dtype)])
                return v
            nl = is_null
            if width < out_cap:
                nl = jnp.concatenate(
                    [nl, jnp.ones((out_cap - width,), bool)])
            cols.append(Decimal128Column(
                *[lane(b) for b in lane_b], nl, t,
                count=(lane(n_per.astype(jnp.int64))
                       if kind == "avg128" else None)))
        elif kind in ("min", "max"):
            pc = page.columns[a.field] if a.field is not None else None
            if pc is not None and not hasattr(pc, "values"):
                # DECIMAL(p>18): exact lexicographic min/max over the
                # carry-normalized limb lanes — narrow the live mask
                # lane by lane (most-significant first); 4 masked
                # reductions, no 128-bit compare needed
                from presto_tpu.data import int128 as I
                from presto_tpu.data.column import Decimal128Column
                norm = I.normalize(pc.value_lanes)
                win_lanes = []
                masks_nar = [lv for lv in live]
                for li, lane_v in enumerate(norm):
                    ident = (jnp.iinfo(jnp.int64).max if kind == "min"
                             else jnp.iinfo(jnp.int64).min)
                    red = jnp.min if kind == "min" else jnp.max
                    w = jnp.stack([red(jnp.where(m, lane_v, ident))
                                   for m in masks_nar])
                    masks_nar = [m & (lane_v == w[bi])
                                 for bi, m in enumerate(masks_nar)]
                    win_lanes.append(w)
                is_null = (n_per == 0)[take] | ~out_valid_w

                def lane_mm(bins_arr, fill=0):
                    v2 = jnp.where(is_null, fill, bins_arr[take])
                    if width < out_cap:
                        v2 = jnp.concatenate(
                            [v2, jnp.full((out_cap - width,), fill,
                                          dtype=v2.dtype)])
                    return v2
                nl2 = is_null
                if width < out_cap:
                    nl2 = jnp.concatenate(
                        [nl2, jnp.ones((out_cap - width,), bool)])
                cols.append(Decimal128Column(
                    *[lane_mm(w) for w in win_lanes], nl2, t))
                continue
            v = vals.astype(jnp.int32) if vals.dtype == jnp.bool_ else vals
            if jnp.issubdtype(v.dtype, jnp.floating):
                ident = jnp.inf if kind == "min" else -jnp.inf
            else:
                info = jnp.iinfo(v.dtype)
                ident = info.max if kind == "min" else info.min
            red = jnp.min if kind == "min" else jnp.max
            r = jnp.stack([red(jnp.where(lv, v, ident)) for lv in live])
            cols.append(widen(r, t, (n_per == 0)[take], dictionary))
        elif kind in ("bool_or", "bool_and"):
            if kind == "bool_or":
                r = jnp.stack([jnp.any(lv & vals.astype(bool))
                               for lv in live])
            else:
                r = jnp.stack([jnp.all(~lv | vals.astype(bool))
                               for lv in live])
            cols.append(widen(r, t, (n_per == 0)[take]))
        elif kind == "approx_distinct":
            import jax

            live_all = valid & ~nulls
            reg, rank = _hll_reg_rank(vals)
            # sort (bin, register, rank desc): the first row of each
            # (bin, register) run holds that register's max rank
            code_s = jnp.where(live_all, code, prod)
            s_ops = jax.lax.sort((code_s, reg, -rank, rank),
                                 num_keys=3, is_stable=False)
            sc, sreg, _nr, srank = s_ops
            first = (jnp.roll(sc, 1) != sc) | (jnp.roll(sreg, 1) != sreg)
            first = first.at[0].set(True)
            first = first & (sc < prod)
            contrib = jnp.where(first,
                                jnp.exp2(-srank.astype(jnp.float64)), 0.0)
            present = jnp.stack([
                jnp.sum(jnp.where(first & (sc == b), contrib, 0.0))
                for b in range(prod)])
            dregs = jnp.stack([jnp.sum(first & (sc == b))
                               for b in range(prod)])
            est = _hll_estimate(present, _HLL_M - dregs)
            est = jnp.where(n_per == 0, 0, jnp.round(est))
            cols.append(widen(est.astype(jnp.int64), t, false_w))
        elif kind == "approx_percentile":
            import jax

            from presto_tpu.ops.keys import _orderable_values

            frac = float(a.param if a.param is not None else 0.5)
            src_t = (page.columns[a.field].type
                     if a.field is not None else t)
            live_all = valid & ~nulls
            ov = _orderable_values(Column(vals, nulls, src_t, dictionary))
            if ov.dtype == jnp.bool_:
                ov = ov.astype(jnp.int32)
            code_s = jnp.where(live_all, code, prod)
            s_ops = jax.lax.sort((code_s, ov, vals), num_keys=2,
                                 is_stable=False)
            svals = s_ops[2]
            live_counts = jnp.stack([jnp.sum(live_all & (code == b))
                                     for b in range(prod)])
            bin_starts = jnp.cumsum(live_counts) - live_counts
            idx = bin_starts + jnp.floor(
                frac * jnp.maximum(live_counts - 1, 0)
                .astype(jnp.float64)).astype(live_counts.dtype)
            picked = jnp.take(svals, jnp.clip(idx, 0, cap - 1),
                              mode="clip")
            cols.append(widen(picked, t, (live_counts == 0)[take],
                              dictionary))
        else:
            raise NotImplementedError(f"aggregate {kind}")

    out_rows = jnp.minimum(num_groups, out_cap)
    return Page(tuple(cols), out_rows, ()), num_groups


def _agg_inputs(a: AggSpec, page: Page):
    """(values, null-or-masked-out) for an aggregate input, unpermuted."""
    if a.field is not None:
        col = page.columns[a.field]
        # Decimal128 inputs have limb lanes, not a single values lane;
        # sum128/avg128 read the lanes themselves — hand them l0 so the
        # null/mask plumbing stays uniform
        vals = col.values if hasattr(col, "values") else col.l0
        nulls = col.nulls
    else:
        vals = jnp.zeros((page.capacity,), dtype=jnp.int64)
        nulls = jnp.zeros((page.capacity,), dtype=bool)
    if a.mask_field is not None:
        m = page.columns[a.mask_field]
        nulls = nulls | ~(~m.nulls & m.values.astype(bool))
    return vals, nulls


def grouped_aggregate(page: Page, group_fields: Sequence[int],
                      aggs: Sequence[AggSpec],
                      out_capacity: Optional[int] = None,
                      row_mask: Optional[jnp.ndarray] = None):
    """Group `page` by `group_fields` and evaluate `aggs`. Output columns:
    group keys (in order) then one column per agg (avg_partial emits two).
    With no group fields, emits exactly one row (SQL global aggregation).
    `row_mask` (bool per row) pre-filters rows without a compaction pass —
    the fused ScanFilterAndProject -> Aggregation pipeline.

    Returns (page, true_group_count): true_group_count is unclamped so the
    host can detect out_capacity overflow and retry at a bigger bucket."""
    cap = page.capacity
    out_cap = out_capacity or cap
    valid = page.row_valid()
    if row_mask is not None:
        valid = valid & row_mask

    if not group_fields:
        # Global aggregation: one bin, pure masked whole-array reductions —
        # never a scatter (XLA serializes colliding-index scatters on TPU).
        # min_groups=1: SQL global aggregation emits exactly one row even
        # over empty input (count()=0, sum()=NULL).
        return _direct_grouped_aggregate(page, (), aggs, out_cap, valid,
                                         [], 1, min_groups=1)

    # Decimal128 merge steps read limb-lane columns — sorted path only
    merge128 = any(a.kind in ("sum128_merge", "avg128_merge")
                   for a in aggs)
    d = None if merge128 else _direct_domains(page, group_fields)
    if d is not None:
        domains, prod = d
        return _direct_grouped_aggregate(
            page, group_fields, aggs, out_cap, valid, domains, prod)
    return _sorted_grouped_aggregate(page, group_fields, aggs, out_cap,
                                     valid)


def _sorted_grouped_aggregate(page: Page, group_fields: Sequence[int],
                              aggs: Sequence[AggSpec], out_cap: int,
                              valid: jnp.ndarray):
    """General (large-domain) grouping: sort a PERMUTATION by the group
    key lanes (composed 2-operand argsorts, ops/keys.lex_perm), gather
    the page by it, then contiguous-segment reductions via blocked
    cumsum (ops/scan.py; scatter-adds serialize on TPU). Wide variadic
    sorts carrying every column as payload are banned — their compile
    cost explodes with operand count on this stack.

    Reference role: HashAggregationOperator over MultiChannelGroupByHash —
    re-expressed as sort + segment reduce because a probe-loop hash table
    has no efficient TPU form, but a sort network does."""
    from presto_tpu.data.column import gather_page
    from presto_tpu.ops import scan as pscan
    from presto_tpu.ops.keys import group_values, lex_perm, values_equal

    cap = page.capacity

    # Sort lanes: invalid rows last, then per group field (nulls last,
    # group-canonical value). The invalid rank folds into the FIRST
    # field's null rank (invalid > null > value) to save one pass.
    inv_rank = (~valid).astype(jnp.int8)
    lanes = []
    for i, f in enumerate(group_fields):
        c = page.columns[f]
        nrank = c.nulls.astype(jnp.int8)
        lanes.append(inv_rank * 2 + nrank if i == 0 else nrank)
        lanes.append(group_values(c))
    if not group_fields:
        lanes.append(inv_rank)
    perm = lex_perm(lanes)
    gvalid = valid[perm]
    sp = gather_page(page, perm)

    # New-group flags from adjacent compare on the sorted key lanes.
    flags = jnp.zeros((cap,), dtype=bool).at[0].set(True)
    for f in group_fields:
        c = sp.columns[f]
        n = c.nulls
        v = group_values(c)
        prev_n = jnp.roll(n, 1)
        prev_v = jnp.roll(v, 1)
        # values_equal: NaN group keys compare equal (SQL grouping)
        same = (values_equal(v, prev_v) & ~n & ~prev_n) | (n & prev_n)
        flags = flags | ~same
    flags = flags.at[0].set(True)

    starts, gid = pscan.group_starts(flags, gvalid, out_cap)
    num_groups = jnp.sum(flags & gvalid).astype(jnp.int32)
    total_valid = jnp.sum(gvalid).astype(jnp.int32)
    g_arange = jnp.arange(out_cap, dtype=jnp.int32)
    out_valid = g_arange < jnp.minimum(num_groups, out_cap)
    nxt = jnp.concatenate([starts[1:], jnp.full((1,), cap, jnp.int32)])
    ends = jnp.where(g_arange + 1 < num_groups, nxt, total_valid)
    ends = jnp.where(out_valid, ends, starts)        # empty for overflow

    cols = []
    for f in group_fields:
        cols.append(sp.columns[f].gather(starts, out_valid))
    for a in aggs:
        cols.extend(_eval_agg_sorted(a, sp, gvalid, gid, starts, ends,
                                     out_valid, pscan))

    return Page(tuple(cols), jnp.minimum(num_groups, out_cap), ()), \
        num_groups


def _eval_agg_sorted(a: AggSpec, sp: Page, gvalid, gid, starts, ends,
                     out_valid, pscan):
    """Evaluate one aggregate over contiguous sorted segments."""
    t = a.output_type
    out_cap = starts.shape[0]
    if a.kind in ("sum128_merge", "avg128_merge"):
        # FINAL step over Decimal128 partial states (limb lanes summed
        # independently — the distributed DECIMAL(38) merge; reference:
        # the FINAL accumulator of DecimalSumAggregation re-expressed
        # over limb lanes). avg128_merge also sums the count column.
        from presto_tpu.data.column import Decimal128Column
        pc = sp.columns[a.field]
        assert isinstance(pc, Decimal128Column), type(pc)
        live = ~pc.nulls & gvalid
        lanes = [pscan.segment_sums(jnp.where(live, x, 0), starts, ends)
                 for x in pc.value_lanes]
        n = pscan.segment_sums(live.astype(jnp.int64), starts, ends)
        count = None
        if a.kind == "avg128_merge":
            cc = sp.columns[a.field2]
            cv = jnp.where(cc.nulls | ~gvalid, 0, cc.values)
            count = pscan.segment_sums(cv.astype(jnp.int64), starts,
                                       ends)
        is_null = (n == 0) | ~out_valid
        return [Decimal128Column(
            *[jnp.where(is_null, 0, x) for x in lanes],
            is_null, t, count=count)]
    if a.field is not None:
        col = sp.columns[a.field]
        if not hasattr(col, "values") \
                and a.kind not in ("sum128", "avg128", "count",
                                   "min", "max"):
            raise NotImplementedError(
                f"{a.kind} over DECIMAL(38) input")
        vals = col.values if hasattr(col, "values") else col.l0
        nulls = col.nulls | ~gvalid
    else:
        vals = jnp.zeros((sp.capacity,), dtype=jnp.int64)
        nulls = ~gvalid
    if a.mask_field is not None:
        m = sp.columns[a.mask_field]
        nulls = nulls | ~(~m.nulls & m.values.astype(bool))

    dictionary = (sp.columns[a.field].dictionary
                  if a.field is not None and t.is_string else None)

    def out(values, nullmask):
        sent = jnp.asarray(t.null_sentinel(), dtype=t.dtype)
        v = jnp.where(nullmask | ~out_valid, sent, values.astype(t.dtype))
        return Column(v, (nullmask | ~out_valid), t, dictionary)

    def seg_count(live_mask):
        return pscan.segment_sums(live_mask.astype(jnp.int32), starts,
                                  ends).astype(jnp.int64)

    kind = a.kind
    if kind == "count_star":
        return [out((ends - starts).astype(jnp.int64),
                    jnp.zeros_like(out_valid))]
    if kind == "count":
        return [out(seg_count(~nulls), jnp.zeros_like(out_valid))]
    if kind in ("sum128", "avg128"):
        # DECIMAL(38) accumulation: inputs as four 32-bit limb lanes
        # (int64 storage decomposes device-side; wide Decimal128 inputs
        # already carry lanes), segment-summed separately — each limb
        # sum fits int64 for any realistic row count, and the exact
        # 128-bit value recombines on the host (reference:
        # UnscaledDecimal128Arithmetic.java; limb lanes because no
        # 128-bit ops lower on TPU)
        from presto_tpu.data.column import Decimal128Column
        pc = sp.columns[a.field]
        in_lanes = (pc.value_lanes if isinstance(pc, Decimal128Column)
                    else Decimal128Column.decompose_int64(vals))
        lanes = [pscan.segment_sums(
            jnp.where(nulls, 0, x.astype(jnp.int64)), starts, ends)
            for x in in_lanes]
        n = seg_count(~nulls)
        is_null = (n == 0) | ~out_valid
        col = Decimal128Column(
            *[jnp.where(is_null, 0, x) for x in lanes],
            is_null, t, count=(n if kind == "avg128" else None))
        return [col]
    if kind in ("sum", "avg", "avg_partial"):
        acc_dtype = jnp.float64 if t.is_floating or kind != "sum" \
            else jnp.int64
        contrib = jnp.where(nulls, 0, vals).astype(acc_dtype)
        s = pscan.segment_sums(contrib, starts, ends)
        n = seg_count(~nulls)
        if acc_dtype == jnp.int64:
            from presto_tpu.expr import errors as E
            fs = pscan.segment_sums(contrib.astype(jnp.float64),
                                    starts, ends)
            E.record(E.OVF_DECIMAL if t.is_decimal else E.OVF_SUM,
                     jnp.any(jnp.abs(fs - s.astype(jnp.float64))
                             > 2.0 ** 62))
        if kind == "sum":
            return [out(s, n == 0)]
        if kind == "avg":
            return [out(s / jnp.maximum(n, 1), n == 0)]
        sum_col = Column(jnp.where(n == 0, jnp.inf, s.astype(jnp.float64)),
                         n == 0, DOUBLE)
        cnt_col = Column(n, jnp.zeros_like(n, dtype=bool), BIGINT)
        return [sum_col, cnt_col]
    if kind == "avg_final":
        cnt_col = sp.columns[a.field2]
        cvals = jnp.where(cnt_col.nulls, 0, cnt_col.values)
        s = pscan.segment_sums(jnp.where(nulls, 0.0, vals)
                               .astype(jnp.float64), starts, ends)
        n = pscan.segment_sums(cvals.astype(jnp.int64), starts, ends)
        return [out(s / jnp.maximum(n, 1), n == 0)]
    if kind in ("min", "max"):
        # Secondary sort keyed by (gid, null-last, value): the winner lands
        # at each segment start. One extra multi-operand sort, no scatter.
        import jax

        from presto_tpu.ops.keys import _orderable_values

        pc_mm = sp.columns[a.field] if a.field is not None else None
        if pc_mm is not None and not hasattr(pc_mm, "values"):
            # DECIMAL(p>18): sort by (gid, null, normalized limb lanes)
            # — lexicographic lane order IS exact 128-bit value order —
            # and gather the winner's lanes at each segment start
            from presto_tpu.data import int128 as I
            from presto_tpu.data.column import Decimal128Column
            norm = I.normalize(pc_mm.value_lanes)
            if kind == "max":
                norm = I.normalize(I.negate(norm))
            s_ops = jax.lax.sort(
                (gid, nulls.astype(jnp.int8)) + tuple(norm) + (nulls,),
                num_keys=6, is_stable=False)
            win = [jnp.take(x, starts, mode="clip") for x in s_ops[2:6]]
            if kind == "max":
                win = list(I.negate(tuple(win)))
            win_nulls = jnp.take(s_ops[6], starts, mode="clip")
            n = seg_count(~nulls)
            is_null = win_nulls | (n == 0) | ~out_valid
            win = [jnp.where(is_null, 0, w) for w in win]
            return [Decimal128Column(*win, is_null, t)]
        v = _orderable_values(Column(vals, nulls, a.output_type if
                                     a.field is None else
                                     sp.columns[a.field].type, dictionary))
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        sort_v = v if kind == "min" else (
            -v if jnp.issubdtype(v.dtype, jnp.floating)
            else -v.astype(jnp.int64))
        s_ops = jax.lax.sort(
            (gid, nulls.astype(jnp.int8), sort_v, vals, nulls),
            num_keys=3, is_stable=False)
        win_vals = jnp.take(s_ops[3], starts, mode="clip")
        win_nulls = jnp.take(s_ops[4], starts, mode="clip")
        n = seg_count(~nulls)
        return [out(win_vals, win_nulls | (n == 0))]
    if kind in ("bool_or", "bool_and"):
        b = vals.astype(bool) & ~nulls
        trues = pscan.segment_sums(b.astype(jnp.int32), starts, ends)
        n = seg_count(~nulls)
        r = (trues > 0) if kind == "bool_or" else (trues == n)
        return [out(r, n == 0)]
    if kind == "approx_distinct":
        import jax

        live = ~nulls
        reg, rank = _hll_reg_rank(vals)
        # rows re-sorted by (gid, register, rank desc); group runs stay
        # contiguous (gid is the primary key), so the original
        # starts/ends still delimit them. Dead rows sort to register M.
        reg_s = jnp.where(live, reg, _HLL_M)
        s_ops = jax.lax.sort((gid, reg_s, -rank, rank, live),
                             num_keys=3, is_stable=False)
        sgid, sreg, _nr, srank, slive = s_ops
        first = jnp.roll(sgid, 1) != sgid
        first = first | (jnp.roll(sreg, 1) != sreg)
        first = first.at[0].set(True)
        first = first & slive
        contrib = jnp.where(first, jnp.exp2(-srank.astype(jnp.float64)),
                            0.0)
        present = pscan.segment_sums(contrib, starts, ends)
        distinct_regs = pscan.segment_sums(first.astype(jnp.int32),
                                           starts, ends)
        est = _hll_estimate(present, _HLL_M - distinct_regs)
        n = seg_count(live)
        # empty group => 0 (Presto approx_distinct over no rows)
        return [out(jnp.where(n == 0, 0,
                              jnp.round(est)).astype(jnp.int64),
                    jnp.zeros_like(out_valid))]
    if kind == "approx_percentile":
        import jax

        from presto_tpu.ops.keys import _orderable_values

        frac = float(a.param if a.param is not None else 0.5)
        v = _orderable_values(Column(vals, nulls, sp.columns[a.field].type,
                                     dictionary))
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        s_ops = jax.lax.sort((gid, nulls.astype(jnp.int8), v, vals),
                             num_keys=3, is_stable=False)
        svals = s_ops[3]
        n = seg_count(~nulls)
        # lower nearest-rank: the element at floor(p * (n-1)) of the
        # group's sorted non-null run (approx contract; exact quantile)
        idx = starts + jnp.floor(
            frac * jnp.maximum(n - 1, 0).astype(jnp.float64)
        ).astype(jnp.int32)
        picked = jnp.take(svals, jnp.clip(idx, 0, sp.capacity - 1),
                          mode="clip")
        return [out(picked, n == 0)]
    raise NotImplementedError(f"aggregate {kind}")
