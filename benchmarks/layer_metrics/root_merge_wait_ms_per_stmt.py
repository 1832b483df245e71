"""Milliseconds the ordered root merge's thread spends blocked on its
streams a statement: the sum of the `blocked_ms` of the program's
`root_merge` spans inside the traced window, over the statements
attempted. 0.0 where merges ran and never waited; nothing where no
`root_merge` span lies in the window (no ordered merge at the root, or a
program without the span)."""

import span_reduce


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None or not any(
            s.name == "root_merge" and s.end_s >= w.lo and s.start_s <= w.hi
            for s in w.spans):
        return None
    return span_reduce.attribute_sum(
        w.spans, "root_merge", "blocked_ms", w.lo, w.hi) / w.statements
