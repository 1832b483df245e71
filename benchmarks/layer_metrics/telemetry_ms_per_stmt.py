"""Milliseconds a statement spends on the program's own observability, on
the statement's thread: the two forced telemetry sweeps, the wide event
with its snapshot, and the scrape of spans from workers of other processes
(the `telemetry` spans), over the statements attempted."""

import span_reduce


def read(ctx):
    return span_reduce.ms_per_stmt(ctx, "telemetry")
