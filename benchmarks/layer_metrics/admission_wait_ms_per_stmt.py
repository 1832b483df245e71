"""Milliseconds a statement waited between its hand-over to the dispatcher
and a pool thread taking it up, plus a blocking admission in the cluster
where the statement server did not admit it: the wall the program's
`admission_wait` spans cover inside the traced window (`span_reduce`), over
the statements attempted. Nothing without a trace or such a span."""

import span_reduce


def read(ctx):
    return span_reduce.ms_per_stmt(ctx, "admission_wait")
