"""Milliseconds of coordinator work a statement: the wall the program's
`plan` (parse, analyze, plan, exchanges, fragments) and `schedule`
(fragments to protocol, stages, task POSTs) spans cover inside the traced
window, over the statements attempted."""

import span_reduce


def read(ctx):
    return span_reduce.ms_per_stmt(ctx, "plan", "schedule")
