"""Device time by what a program's `dispatch` span says of it beyond its
operators: `join_types` (INNER, LEFT, FULL, SEMI, ANTI, a JoinNode each)
and `agg_steps` (PARTIAL, FINAL, SINGLE, an AggregationNode each), both
joined by "+". A program without the attribute (one that neither joins
nor aggregates, or one of a tree that does not set it) is not chosen."""

from __future__ import annotations

import span_reduce


def programs_with(spans, key: str, value: str) -> set:
    """The programs whose `dispatch` span lists `value` under `key`."""
    return {str(s.stats["program"]) for s in spans
            if s.name == "dispatch" and "program" in s.stats
            and value in str(s.stats.get(key, "")).split("+")}


def device_ms_per_stmt(ctx: dict, key: str, value: str):
    """Device milliseconds inside the programs that list `value` under
    `key`, over the statements attempted; None where no such program ran
    in the window."""
    w = span_reduce.window(ctx)
    if w is None:
        return None
    chosen = programs_with(w.spans, key, value)
    seconds = sum(s for name, (s, _n) in span_reduce.module_seconds(
        w.modules, w.lo, w.hi).items() if name in chosen)
    return 1e3 * seconds / w.statements if seconds > 0 else None
