"""Blocked scan primitives.

On this TPU stack a 1-D `jnp.cumsum` over a 16M-element array takes
minutes to *compile* (XLA unrolls the log-N scan over one huge dimension)
and scatter-adds serialize per colliding index (~1.6 s for 16M->64k), so
neither is usable as a segment-reduction mechanism. These helpers reshape
to [blocks, lane] and scan hierarchically: an intra-block scan over the
small trailing axis (a handful of shifted adds the compiler handles well),
a tiny scan over per-block totals, and a broadcast combine. Compiles in
seconds, runs at memory bandwidth.

Reference role: these stand in for the sequential accumulator loops inside
the reference's operators (e.g. cumulative counts in
presto-main-base/.../operator/GroupByIdBlock / window frame offsets) —
re-expressed as data-parallel scans.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


_LANE = 2048  # trailing-axis width; power of two, fits VMEM comfortably


def _pad_to_blocks(x: jnp.ndarray):
    n = x.shape[0]
    blocks = max(1, (n + _LANE - 1) // _LANE)
    pad = blocks * _LANE - n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), dtype=x.dtype)])
    return x.reshape(blocks, _LANE), n


def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive 1-D cumulative sum, blocked. Same result as jnp.cumsum."""
    x2, n = _pad_to_blocks(x)
    within = jnp.cumsum(x2, axis=1)                 # [B, LANE]
    totals = within[:, -1]                          # [B]
    offsets = jnp.cumsum(totals) - totals           # exclusive block prefix
    out = within + offsets[:, None]
    return out.reshape(-1)[:n]


def _cummax_1d_doubling(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running max of a SMALL 1-D array via Hillis-Steele
    doubling (log n shifted-max steps — plain elementwise ops, never
    lax.associative_scan, whose custom-op lowering compiles
    pathologically on this stack)."""
    n = x.shape[0]
    lo = jnp.full((1,), jnp.iinfo(x.dtype).min, x.dtype)
    d = 1
    while d < n:
        pad = jnp.broadcast_to(lo, (d,))
        x = jnp.maximum(x, jnp.concatenate([pad, x[:-d]]))
        d *= 2
    return x


def blocked_cummax(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running max, blocked: doubling scan over the [B, LANE]
    trailing axis + tiny block-prefix scan + combine."""
    x2, n = _pad_to_blocks(x)
    lo = jnp.iinfo(x.dtype).min
    if n < x2.size:                 # padding must not win the max
        flat = x2.reshape(-1)
        flat = jnp.where(jnp.arange(flat.shape[0]) < n, flat, lo)
        x2 = flat.reshape(x2.shape)
    within = x2
    d = 1
    while d < _LANE:
        shifted = jnp.concatenate(
            [jnp.full((within.shape[0], d), lo, within.dtype),
             within[:, :-d]], axis=1)
        within = jnp.maximum(within, shifted)
        d *= 2
    totals = within[:, -1]
    pre = _cummax_1d_doubling(totals)
    pre = jnp.concatenate([jnp.full((1,), lo, x.dtype), pre[:-1]])
    return jnp.maximum(within, pre[:, None]).reshape(-1)[:n]


def fill_forward(vals: jnp.ndarray, present: jnp.ndarray,
                 init=None):
    """Per-slot last `present` value at or before the slot. Slots before
    the first present value get `init` (default: the dtype's zero). The
    merge-join propagation primitive.

    Implemented as a blocked running-max of present POSITIONS + one
    gather (never a value-carrying associative_scan: its custom-op
    lowering compiles pathologically on this stack). On the chip the
    running max is under 1 ms at 3 M slots and the gather is the cost:
    7-9 ns an element for every 32-bit lane, ~20 ns where the table is
    out of near memory (50-62 ms for 2-3 M elements; ledger, PR 26). A
    value narrow enough to sit below the position in ONE 64-bit running
    max needs no gather at all (ops/join.merge_join)."""
    if init is None:
        init = jnp.zeros((), dtype=vals.dtype)
    n = vals.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    pos = jnp.where(present, idx, jnp.int32(-1))
    last = blocked_cummax(pos)
    out = jnp.take(vals, jnp.clip(last, 0, n - 1), mode="clip")
    return jnp.where(last >= 0, out, jnp.asarray(init, vals.dtype))


def seg_scan(vals: jnp.ndarray, seg_start: jnp.ndarray, binop,
             ident) -> jnp.ndarray:
    """Inclusive segmented scan: out[i] = binop-fold of vals over
    [start_of_segment(i), i], where True in `seg_start` begins a new
    segment. `ident` is binop's identity (used for padding and
    pre-first-segment slots). The running min/max window-frame
    primitive.

    Hillis-Steele doubling over the blocked [B, LANE] layout — plain
    shifted elementwise steps, never lax.associative_scan (its
    custom-op lowering compiles pathologically on this stack)."""
    n0 = vals.shape[0]
    blocks = max(1, (n0 + _LANE - 1) // _LANE)
    pad = blocks * _LANE - n0
    if pad:
        vals = jnp.concatenate(
            [vals, jnp.full((pad,), ident, vals.dtype)])
        seg_start = jnp.concatenate(
            [seg_start, jnp.zeros((pad,), bool)])
    v = vals.reshape(blocks, _LANE)
    f = seg_start.reshape(blocks, _LANE)

    # segmented doubling along the lane axis: fold in the value d slots
    # left unless a segment boundary lies in between (the or-accumulated
    # flag blocks propagation across starts)
    d = 1
    while d < _LANE:
        v_sh = jnp.concatenate(
            [jnp.full((blocks, d), ident, v.dtype), v[:, :-d]], axis=1)
        f_sh = jnp.concatenate(
            [jnp.zeros((blocks, d), bool), f[:, :-d]], axis=1)
        v = jnp.where(f, v, binop(v, v_sh))
        f = f | f_sh
        d *= 2
    # tiny exclusive prefix over the per-block (total, has-boundary)
    bv, bf = v[:, -1], f[:, -1]
    db = 1
    while db < blocks:
        bv_sh = jnp.concatenate(
            [jnp.full((db,), ident, bv.dtype), bv[:-db]])
        bf_sh = jnp.concatenate([jnp.zeros((db,), bool), bf[:-db]])
        bv = jnp.where(bf, bv, binop(bv, bv_sh))
        bf = bf | bf_sh
        db *= 2
    pv = jnp.concatenate([jnp.full((1,), ident, bv.dtype), bv[:-1]])
    out = jnp.where(f, v, binop(pv[:, None], v))
    return out.reshape(-1)[:n0]


def fill_backward(vals: jnp.ndarray, present: jnp.ndarray, init=None):
    """Per-slot next `present` value at or after the slot (reversed
    fill_forward; flips lower to strided slices, not gathers)."""
    rev = lambda a: jnp.flip(a, axis=0)          # noqa: E731
    return rev(fill_forward(rev(vals), rev(present), init))


def segment_sums(vals: jnp.ndarray, starts: jnp.ndarray,
                 ends: jnp.ndarray) -> jnp.ndarray:
    """Per-segment sums over *contiguous* segments (rows pre-sorted by
    group). starts/ends are [G] row ranges per segment (end exclusive).
    Uses one blocked cumsum + two small gathers — no scatter."""
    acc = (jnp.float64 if jnp.issubdtype(vals.dtype, jnp.floating)
           else jnp.int64)
    cs = cumsum(vals.astype(acc))
    cap = vals.shape[0]
    hi = jnp.take(cs, jnp.clip(ends - 1, 0, cap - 1), mode="clip")
    lo = jnp.where(starts > 0,
                   jnp.take(cs, jnp.clip(starts - 1, 0, cap - 1),
                            mode="clip"),
                   jnp.zeros((), dtype=acc))
    return jnp.where(ends > starts, hi - lo, 0)


def group_starts(flags: jnp.ndarray, gvalid: jnp.ndarray, out_cap: int):
    """Given sorted new-group flags + per-row validity, return
    (starts[out_cap], gid[rows]) where starts[g] is the first row of
    group g and invalid rows map to the overflow bin gid == out_cap.

    Implemented with one small multi-operand sort over row indices: rows
    that start a group sort first by group id, giving the start offsets
    densely — no scatter, no big searchsorted."""
    cap = flags.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    live_flag = flags & gvalid
    gid = cumsum(live_flag.astype(jnp.int32)) - 1
    gid = jnp.where(gvalid, gid, out_cap)
    # Sort group-start rows to the front, ordered by gid (== row order).
    key = jnp.where(live_flag, idx, cap + idx)
    import jax.lax
    _key, starts_sorted = jax.lax.sort((key, idx), num_keys=1)
    starts = starts_sorted[:out_cap]
    if cap < out_cap:
        # the page has fewer rows than the requested group capacity
        # (per-device shards of a plan whose group estimate was sized
        # for the whole table): pad with `cap` so the contract
        # starts[out_cap] holds — padded bins are masked invalid by the
        # caller's out_valid and their segments are empty
        starts = jnp.concatenate(
            [starts, jnp.full((out_cap - cap,), cap, jnp.int32)])
    return starts, gid
