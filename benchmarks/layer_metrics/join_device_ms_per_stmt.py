"""Device milliseconds a statement inside the programs that hold a join:
the modules (busiest device, traced window) whose `dispatch` span lists
`Join` among its `operators`, over the statements attempted."""

import span_reduce


def read(ctx):
    return span_reduce.device_ms_per_stmt(ctx, "Join")
