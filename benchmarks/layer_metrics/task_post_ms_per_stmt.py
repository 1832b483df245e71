"""Milliseconds of TaskUpdateRequest round trips a statement: the SUM of
the program's `task_post` spans (one for each task the coordinator posts,
the worker's `task_create` inside) inside the traced window, over the
statements attempted. A sum and not a union, so posts made at once still
count each: such a change moves `coordinator_ms_per_stmt` and leaves this,
a cheaper post moves both. Nothing where the program has no such span."""

import span_reduce


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None:
        return None
    seconds = sum(max(0.0, min(s.end_s, w.hi) - max(s.start_s, w.lo))
                  for s in w.spans if s.name == "task_post")
    return 1e3 * seconds / w.statements if seconds > 0 else None
