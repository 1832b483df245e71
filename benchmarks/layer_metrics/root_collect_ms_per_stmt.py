"""Milliseconds the coordinator spends bringing a statement's result
home: the `collect_root` spans (pull of the root stage's buffers, decode,
rows as Python tuples, the ordered merge; the statement server's row
encoding), over the statements attempted."""

import span_reduce


def read(ctx):
    return span_reduce.ms_per_stmt(ctx, "collect_root")
