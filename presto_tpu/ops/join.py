"""Joins — a sort-merge join for unique build keys, and the expansion join.

Reference roles: HashBuilderOperator/LookupJoinOperator
(presto-main-base/.../operator/HashBuilderOperator.java:55,
LookupJoinOperator.java:52 over PagesHash/JoinProbe), HashSemiJoinOperator,
NestedLoopJoinOperator, MergeOperator. TPU-first redesign, no
pointer-chasing hash table, in two forms:

`merge_join` — what every equi-join lowers onto first (FK joins: the build
keys are unique; semi/anti, where duplicates change nothing). One sort over
build ++ probe keyed on the key values, one running max in sorted order,
one sort back. Laid out on what the chip measured (PERF.md, PR 27): a sort
of 3 M rows with three 32-bit operands takes 7 ms and a scan under 1 ms,
while every 1-D gather takes 7-9 ns an element (15-19 ms for ONE 32-bit lane
of 2 M rows, 59 ms where the table does not sit in near memory) — so no lane
is fetched by a permutation that a sort can carry or a scan can propagate,
and the payload columns move once, stacked: a [k, n] gather along n costs
what one lane costs for k up to 8. Sorts are dear to COMPILE instead, and by
operand: ~40 s for two, +25 s for each one more, twice that when stable — so
each sort carries one lane beside its keys and none asks for stability.

`hash_join` — the expansion join, for duplicate build keys and cross joins:
the build side is sorted by a 64-bit key hash (one argsort), probes binary-
search the sorted hashes (jnp.searchsorted is vectorized), and the variable
match fan-out is materialized by a prefix-sum pair expansion into a page of
*static* capacity. Hash-equal-but-key-unequal pairs (collisions, multi-key)
are masked by an exact key comparison on the expanded pairs. Capacity
contract: like aggregation, `out_capacity` bounds the join output;
`total_pairs` (traced) lets the executor detect overflow and retry at a
larger bucket.

NULL join keys never match (SQL semantics): merge_join's null-key rows take
no part in its scan; hash_join tags them with disjoint sentinel hashes on
each side.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
from jax import lax

from presto_tpu.data.column import Column, Page
from presto_tpu.expr.compile import align_string_columns
from presto_tpu.ops.keys import group_values, hash_columns, \
    values_equal
from presto_tpu.ops.scan import blocked_cummax


def _aligned_keys(probe: Page, build: Page, probe_fields, build_fields):
    """Pull key columns, aligning string dictionaries across sides."""
    pcols, bcols = [], []
    for pf, bf in zip(probe_fields, build_fields):
        pc, bc = probe.columns[pf], build.columns[bf]
        if pc.type.is_string and bc.type.is_string:
            pc, bc = align_string_columns(pc, bc)
        pcols.append(pc)
        bcols.append(bc)
    return pcols, bcols


#: 32-bit lanes one stacked gather moves. On the chip a [k, n] gather
#: along n costs what ONE 1-D gather of n costs for k up to 8 (11-13 ms
#: against 15-19 ms a lane at 2 M rows; PERF.md, PR 27).
_STACK = 8


def _stacked_take(lanes, idx):
    """lanes[j][idx] for same-dtype 1-D lanes, _STACK lanes a gather."""
    out = []
    for at in range(0, len(lanes), _STACK):
        chunk = lanes[at:at + _STACK]
        if len(chunk) == 1:
            out.append(jnp.take(chunk[0], idx, mode="clip"))
        else:
            got = jnp.take(jnp.stack(chunk), idx, axis=1, mode="clip")
            out.extend(got[j] for j in range(len(chunk)))
    return out


def _gather_columns(cols, idx, valid):
    """[c.gather(idx, valid) for c in cols], moved in as few gathers as
    the dtypes allow: every plain column's 32-bit pieces — int32 lanes,
    the two halves of 64-bit integers, float32 bits, and all null flags
    and booleans packed 31 to a word — ride one stack, DOUBLEs another
    (the chip's float64 cannot be split into words: no 64-bit bitcast).
    Other column classes gather themselves."""
    words, doubles, flags, plan = [], [], [], []
    for c in cols:
        dt = c.values.dtype if type(c) is Column else None
        if dt == jnp.bool_:
            kind, at = "flag", len(flags)
            flags.append(c.values)
        elif dt == jnp.float64:
            kind, at = "f64", len(doubles)
            doubles.append(c.values)
        elif dt == jnp.float32:
            kind, at = "f32", len(words)
            words.append(lax.bitcast_convert_type(c.values, jnp.int32))
        elif dt == jnp.int64:
            kind, at = "i64", len(words)
            words += [(c.values >> 32).astype(jnp.int32),
                      c.values.astype(jnp.int32)]
        elif dt is not None and jnp.issubdtype(dt, jnp.signedinteger):
            kind, at = "i32", len(words)
            words.append(c.values.astype(jnp.int32))
        else:
            plan.append((c, None, None, None))
            continue
        plan.append((c, kind, at, len(flags)))
        flags.append(c.nulls)
    first_pack = len(words)
    for at in range(0, len(flags), 31):
        word = jnp.zeros(flags[at].shape, jnp.int32)
        for bit, f in enumerate(flags[at:at + 31]):
            word = word | (f.astype(jnp.int32) << bit)
        words.append(word)
    words = _stacked_take(words, idx)
    doubles = _stacked_take(doubles, idx)

    def flag(i):
        return ((words[first_pack + i // 31] >> (i % 31)) & 1
                ).astype(bool)

    out = []
    for c, kind, at, null_at in plan:
        if kind is None:
            out.append(c.gather(idx, valid))
            continue
        if kind == "flag":
            vals = flag(at)
        elif kind == "f64":
            vals = doubles[at]
        elif kind == "f32":
            vals = lax.bitcast_convert_type(words[at], jnp.float32)
        elif kind == "i64":
            vals = (words[at].astype(jnp.int64) << 32) \
                | (words[at + 1].astype(jnp.int64) & 0xFFFFFFFF)
        else:
            vals = words[at].astype(c.values.dtype)
        sent = jnp.asarray(c.type.null_sentinel(), dtype=c.values.dtype)
        out.append(Column(jnp.where(valid, vals, sent),
                          jnp.where(valid, flag(null_at), True),
                          c.type, c.dictionary))
    return out


def _key_lanes(pc: Column, bc: Column):
    """One key column's comparison lanes (probe, build), of one dtype.
    A key both of whose sides are stored in at most 32 bits (INTEGER,
    DATE, dictionary codes, BOOLEAN) stays int32 — half the sort's key
    bytes on a chip that emulates 64-bit lanes as pairs; every other
    pair takes group_values' 64-bit image."""
    def narrow(c):
        dt = c.values.dtype
        return dt == jnp.bool_ or (jnp.issubdtype(dt, jnp.integer)
                                   and dt.itemsize <= 4)
    if narrow(pc) and narrow(bc):
        return pc.values.astype(jnp.int32), bc.values.astype(jnp.int32)
    return group_values(pc), group_values(bc)


def merge_join(probe: Page, build: Page,
               probe_fields: Sequence[int], build_fields: Sequence[int],
               join_type: str = "inner",
               ) -> Tuple[Page, jnp.ndarray, jnp.ndarray]:
    """Sort-merge join for UNIQUE build keys (+ semi/anti, where
    duplicates cannot change the answer). The lanes ride the sorts; the
    payload is gathered once (the module docstring has the chip's prices).

      1. ONE sort over concatenate([build, probe]), keyed on each key
         column's stored values and then on a tag, 2 * position + "takes
         part" (live, no NULL key). The tag makes every row distinct, so
         the order is a stable sort's — build rows before probe rows
         within a key run — and it is all the sort carries. NULL-key and
         dead rows sort wherever their stored values fall: they take no
         part below, so neither a nulls lane nor a liveness lane is a key.
      2. ONE running max in sorted order (ops/scan.blocked_cummax, 64-bit)
         gives every slot the newest build row that takes part and says
         whether it lies in the slot's own key run: a probe slot matches
         iff it does. The same scan counts duplicate build keys.
      3. ONE sort back, keyed on the position, with the matched build row
         as its payload. For inner it puts the matched probe rows first,
         so it is the compaction as well. Then each side's columns move in
         one stacked gather of their words and one of their DOUBLEs
         (`_gather_columns`); for left/full/semi/anti the probe columns
         do not move at all.

    Returns (page, dup_count, match) where dup_count > 0 means the build
    side had duplicate live keys: for inner/left/full the caller must
    fall back to the expansion join (hash_join); semi/anti results stay
    valid. `match` is the per-probe-row match flag in probe order for
    left/full (None otherwise) — outer-join residual filters need it to
    demote failed matches to null-extensions. Output layout matches
    hash_join: probe cols ++ build cols (inner/left/full; full appends
    the unmatched build rows null-extended on the probe side), or probe
    cols ++ match flag (semi/anti/anti_exists).

    Reference roles: MergeJoinNode / sorted-exchange MergeOperator
    (presto-main-base/.../operator/MergeOperator.java) fused with the
    LookupJoin contract (LookupJoinOperator.java:52).
    """
    pcap, bcap = probe.capacity, build.capacity
    cap = bcap + pcap
    pcols, bcols = _aligned_keys(probe, build, probe_fields, build_fields)

    p_null = jnp.zeros((pcap,), dtype=bool)
    for c in pcols:
        p_null = p_null | c.nulls
    b_null = jnp.zeros((bcap,), dtype=bool)
    for c in bcols:
        b_null = b_null | c.nulls

    def cat(b, p):
        return jnp.concatenate([b, p])

    # Sort operands: one value lane per key column + the tag.
    operands = []
    for pc, bc in zip(pcols, bcols):
        pv, bv = _key_lanes(pc, bc)
        operands.append(cat(bv, pv))
    idx = jnp.arange(cap, dtype=jnp.int32)
    takes_part = cat(build.row_valid() & ~b_null,
                     probe.row_valid() & ~p_null)
    # The tag is the last KEY, so no two rows compare equal: the order
    # is that of a stable sort without asking for one (is_stable doubles
    # the sort's compile time on the TPU: 109 s against 55 s here).
    *s_keys, s_tag = lax.sort(operands + [2 * idx + takes_part],
                              num_keys=len(operands) + 1, is_stable=False)
    s_pos = s_tag >> 1             # position in the concatenation
    s_part = (s_tag & 1).astype(bool)
    s_present = s_part & (s_pos < bcap)    # live build row, non-null key
    s_probe = s_part & (s_pos >= bcap)     # live probe row, non-null key

    # Key runs: a slot continues its predecessor's run iff every key
    # column's stored value is equal (NaN == NaN).
    same_key = jnp.ones((cap,), bool)
    for kv in s_keys:
        same_key = same_key & values_equal(kv, jnp.roll(kv, 1))
    same_key = same_key.at[0].set(False)

    # One running max answers "which is the last present build row" and
    # "does it lie in this slot's run" at once: mark run starts 2i and
    # present rows 2i + 1 in the high word, with the build row below —
    # the newest mark is odd iff no run began after the newest present
    # row, and its low word is that row. No gather: a scan is ~1 ms here.
    hi = (2 * idx).astype(jnp.int64) << 32
    mark = blocked_cummax(jnp.where(
        s_present, hi | (jnp.int64(1) << 32) | s_pos.astype(jnp.int64),
        jnp.where(same_key, jnp.int64(-1), hi)))
    in_run = ((mark >> 32) & 1).astype(bool)
    # Duplicate live build keys: a present build row whose run already
    # holds one (rows that take no part may lie between the two).
    dup_count = jnp.sum(s_present & same_key & jnp.roll(in_run, 1)
                        ).astype(jnp.int64)
    s_ff = jnp.where(s_probe & in_run, mark.astype(jnp.int32) + 1, 0)

    if join_type == "inner":
        # Matched probe rows first, in probe order: restore + compact.
        order = jnp.where(s_ff > 0, s_pos, s_pos + cap)
        r_pos, r_ff = lax.sort((order, s_ff), num_keys=1, is_stable=False)
        n = jnp.sum(s_ff > 0).astype(jnp.int32)
        valid = jnp.arange(pcap, dtype=jnp.int32) < n
        prow = jnp.where(valid, r_pos[:pcap] - bcap, 0)
        brow = jnp.where(valid, r_ff[:pcap] - 1, 0)
        cols = _gather_columns(probe.columns, prow, valid) \
            + _gather_columns(build.columns, brow, valid)
        return Page(tuple(cols), n, ()), dup_count, None

    payload = [s_ff]
    if join_type == "full":
        # A present build row is matched iff a live non-null-key probe
        # row follows it inside its run: the same running max, backward,
        # over run ends (2i) and such probe rows (2i + 1).
        run_end = jnp.roll(~same_key, -1).at[-1].set(True)
        ahead = 2 * (cap - 1 - idx)
        back = blocked_cummax(jnp.where(
            s_probe, ahead + 1, jnp.where(run_end, ahead, -1))[::-1])
        payload.append(s_present & (back[::-1] & 1).astype(bool))
    _r_pos, *restored = lax.sort([s_pos] + payload, num_keys=1,
                                 is_stable=False)
    ffq = restored[0][bcap:]                     # probe order
    match_p = ffq > 0

    if join_type in ("semi", "anti", "anti_exists"):
        p_live = probe.row_valid()
        if join_type == "semi":
            flag = match_p
        elif join_type == "anti_exists":
            flag = ~match_p & p_live
        else:
            b_has_null = jnp.any(b_null & build.row_valid())
            flag = ~match_p & ~p_null & ~b_has_null & p_live
        col = Column(flag, jnp.zeros((pcap,), bool), _bool_type(), None)
        out = Page(probe.columns + (col,), probe.num_rows, ())
        return out, dup_count, None

    # Probe columns stay where they are; the build payload lands by one
    # gather in probe order.
    bidx = jnp.maximum(ffq - 1, 0)
    out_cols = list(probe.columns) \
        + _gather_columns(build.columns, bidx, match_p)
    page = Page(tuple(out_cols), probe.num_rows, ())
    if join_type == "left":
        return page, dup_count, match_p
    unmatched = build.row_valid() & ~restored[1][:bcap]
    return full_outer_append(page, probe, build, unmatched), dup_count, \
        match_p


def full_outer_append(left_page: Page, probe: Page, build: Page,
                      unmatched_build: jnp.ndarray) -> Page:
    """Append unmatched build rows (probe side null) to a left-join page.
    Output capacity = pcap + bcap, survivors compacted with one sort."""
    from presto_tpu.data.column import compact

    pcap, bcap = probe.capacity, build.capacity
    cols = []
    for i, c in enumerate(left_page.columns):
        if i < len(probe.columns):
            t = probe.columns[i].type
            pad_v = jnp.full((bcap,), t.null_sentinel(), dtype=c.values.dtype)
            vals = jnp.concatenate([c.values, pad_v])
            nulls = jnp.concatenate([c.nulls, jnp.ones((bcap,), bool)])
        else:
            b = build.columns[i - len(probe.columns)]
            vals = jnp.concatenate([c.values, b.values])
            nulls = jnp.concatenate([c.nulls, b.nulls])
        cols.append(Column(vals, nulls, c.type, c.dictionary))
    keep = jnp.concatenate([
        jnp.arange(pcap, dtype=jnp.int32) < left_page.num_rows,
        unmatched_build])
    n = jnp.sum(keep).astype(jnp.int32)
    page = Page(tuple(cols), jnp.asarray(pcap + bcap, jnp.int32), ())
    out = compact(page, keep)
    return Page(out.columns, n, ())


def hash_join(probe: Page, build: Page,
              probe_fields: Sequence[int], build_fields: Sequence[int],
              out_capacity: int, join_type: str = "inner",
              ) -> Tuple[Page, jnp.ndarray]:
    """Join probe x build. Output columns = probe columns ++ build columns
    (for semi/anti: probe columns only). Returns (page, total_pairs) where
    total_pairs > out_capacity indicates overflow (host retries bigger).

    join_type: inner | left | semi | anti. ("left" = probe-outer, matching
    the planner's probe/build orientation, cf. JoinNode probe=left child.)
    """
    pcap, bcap = probe.capacity, build.capacity
    if probe_fields:
        pcols, bcols = _aligned_keys(probe, build, probe_fields,
                                     build_fields)
        ph = hash_columns(pcols)
        bh = hash_columns(bcols)
    else:
        # cross join: constant key — every live row pairs with every live row
        pcols, bcols = [], []
        ph = jnp.zeros((pcap,), dtype=jnp.int64)
        bh = jnp.zeros((bcap,), dtype=jnp.int64)

    p_null = jnp.zeros((pcap,), dtype=bool)
    for c in pcols:
        p_null = p_null | c.nulls
    b_null = jnp.zeros((bcap,), dtype=bool)
    for c in bcols:
        b_null = b_null | c.nulls

    # Disjoint sentinels so null/padding keys can never pair up.
    p_live = probe.row_valid() & ~p_null
    b_live = build.row_valid() & ~b_null
    ph = jnp.where(p_live, ph, jnp.int64(-1))
    bh = jnp.where(b_live, bh, jnp.int64(-2))

    order = jnp.argsort(bh, stable=True)
    bh_sorted = bh[order]

    lo = jnp.searchsorted(bh_sorted, ph, side="left")
    hi = jnp.searchsorted(bh_sorted, ph, side="right")
    counts = jnp.where(p_live, hi - lo, 0).astype(jnp.int64)

    if join_type in ("semi", "anti", "anti_exists"):
        # Need >=1 *true* match; verify keys over the candidate window via a
        # bounded scan on the max bucket width (collision windows are tiny).
        matched = _window_any_match(pcols, bcols, order, lo, counts)
        if join_type == "semi":
            flag = matched
        elif join_type == "anti_exists":
            # NOT EXISTS: null keys simply never match; non-matching rows
            # survive (no three-valued NOT IN poisoning).
            flag = ~matched
        else:
            # SQL NOT IN: if the build side contains ANY null key, every
            # non-match is UNKNOWN -> anti join emits nothing; a null probe
            # key is likewise never anti-matched.
            b_has_null = jnp.any(b_null & build.row_valid())
            flag = ~matched & ~p_null & ~b_has_null
        col = Column(flag, jnp.zeros((pcap,), dtype=bool), _bool_type(), None)
        out = Page(probe.columns + (col,), probe.num_rows, ())
        return out, jnp.sum(counts)

    if join_type == "left":
        counts = jnp.where(p_live | (probe.row_valid() & ~p_live),
                           jnp.maximum(counts, jnp.where(
                               probe.row_valid(), 1, 0)), counts)
        # rows with no candidates still emit one (null-extended) pair
    from presto_tpu.ops.scan import cumsum as blocked_cumsum
    cum = blocked_cumsum(counts)     # jnp.cumsum at 8M is pathological
    total = cum[-1] if pcap > 0 else jnp.int64(0)

    j = jnp.arange(out_capacity, dtype=jnp.int64)
    pair_valid = j < total
    pidx = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    pidx_c = jnp.clip(pidx, 0, pcap - 1)
    start = cum[pidx_c] - counts[pidx_c]
    offset = j - start
    bpos = (lo[pidx_c] + offset).astype(jnp.int32)
    real_candidate = (offset < (hi[pidx_c] - lo[pidx_c])) & p_live[pidx_c]
    bidx = order[jnp.clip(bpos, 0, bcap - 1)]

    # Exact key equality on expanded pairs (kills hash collisions).
    key_eq = jnp.ones((out_capacity,), dtype=bool)
    for pc, bc in zip(pcols, bcols):
        pv = group_values(pc)[pidx_c]
        bv = group_values(bc)[bidx]
        key_eq = key_eq & values_equal(pv, bv)
    match = pair_valid & real_candidate & key_eq

    if join_type == "inner":
        keep = match
        build_valid = match
    else:  # left: non-candidate expansion rows become null-extended rows
        keep = pair_valid
        build_valid = match

    out_cols = [c.gather(pidx_c, keep) for c in probe.columns]
    out_cols += [c.gather(bidx, build_valid) for c in build.columns]

    # Compact survivors to the front.
    cap = out_capacity
    order_key = jnp.where(keep, 0, cap) + jnp.arange(cap, dtype=jnp.int64)
    perm = jnp.argsort(order_key)
    n = jnp.sum(keep).astype(jnp.int32)
    valid = jnp.arange(cap, dtype=jnp.int64) < n
    out_cols = tuple(c.gather(perm, valid & jnp.ones_like(valid))
                     for c in out_cols)
    return Page(out_cols, n, ()), total


_UNROLLED_BUCKET_SCAN = 4  # unrolled fast path for typical window widths


def _window_any_match(pcols, bcols, order, lo, counts):
    """For each probe row: any true key match within its hash window.

    The first few slots are unrolled (equal-hash windows are almost always
    a handful of duplicate keys); the remainder — wide duplicate runs or a
    collision pileup — is scanned exactly by a fori_loop whose trip count
    is the *traced* max window width, so arbitrarily wide windows are
    correct, not just "vanishingly unlikely to be wrong"."""
    import jax

    pcap = pcols[0].capacity
    bcap = bcols[0].capacity
    pvals = [group_values(pc) for pc in pcols]
    pnulls = [pc.nulls for pc in pcols]
    # Gather build keys into hash-sorted order once; slot k of probe row i
    # is then sorted position lo[i]+k.
    bvals = [group_values(bc)[order] for bc in bcols]
    bnulls = [bc.nulls[order] for bc in bcols]

    def slot_match(k, matched):
        in_win = k < counts
        bpos = jnp.clip(lo + k, 0, bcap - 1).astype(jnp.int32)
        eq = in_win
        for pv, pn, bv, bn in zip(pvals, pnulls, bvals, bnulls):
            eq = eq & values_equal(pv, bv[bpos]) & ~pn & ~bn[bpos]
        return matched | eq

    matched = jnp.zeros((pcap,), dtype=bool)
    for k in range(_UNROLLED_BUCKET_SCAN):
        matched = slot_match(k, matched)

    # Early exit: a row needs further slots only while it is unmatched and
    # its window extends past k — so a million duplicates of one build key
    # stop after their probe rows match at slot 0 instead of serializing
    # the scan for the whole page.
    def cond(state):
        k, matched = state
        return jnp.any(~matched & (counts > k))

    def body(state):
        k, matched = state
        return k + 1, slot_match(k, matched)

    _, matched = jax.lax.while_loop(
        cond, body, (jnp.int64(_UNROLLED_BUCKET_SCAN), matched))
    return matched


def _bool_type():
    from presto_tpu.types import BOOLEAN
    return BOOLEAN
