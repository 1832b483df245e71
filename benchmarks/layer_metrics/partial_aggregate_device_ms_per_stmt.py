"""Device milliseconds a statement inside the programs that hold a
partial aggregation: the modules (busiest device, traced window) whose
`dispatch` span lists `PARTIAL` among its `agg_steps`, over the
statements attempted. A program counts whole, with the scan or the join
fused into it."""

import dispatch_attrs


def read(ctx):
    return dispatch_attrs.device_ms_per_stmt(ctx, "agg_steps", "PARTIAL")
