"""Device milliseconds a statement inside the programs that hold an
aggregation and no join (a program counts once, so this and
`join_device_ms_per_stmt` sum to no more than `device_busy_ms_per_stmt`),
over the statements attempted."""

import span_reduce


def read(ctx):
    return span_reduce.device_ms_per_stmt(ctx, "Aggregation", without="Join")
