"""Milliseconds a statement's worker tasks spend getting programs ready
that are not backend compiles: `task_plan` (fragment to plan, executor
built anew) and `dispatch` (the call of each island's jitted function: on a
new executor Python tracing, lowering, the compile cache's read, enqueue)
less the host's compile intervals; union over threads, over the statements
attempted. `compile_s_per_stmt` counts the compiles themselves."""

import span_reduce
from trace_reduce import total, union


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None:
        return None
    seconds = total(union(
        span_reduce.covered(w.spans, ("task_plan",), w.lo, w.hi)
        + span_reduce.lowering(w.spans, w.compiling, w.lo, w.hi)))
    return 1e3 * seconds / w.statements if seconds > 0 else None
