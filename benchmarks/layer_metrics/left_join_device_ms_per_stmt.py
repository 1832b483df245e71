"""Device milliseconds a statement inside the programs that hold a LEFT
join: the modules (busiest device, traced window) whose `dispatch` span
lists `LEFT` among its `join_types`, over the statements attempted. A
program counts whole, with whatever else it holds."""

import dispatch_attrs


def read(ctx):
    return dispatch_attrs.device_ms_per_stmt(ctx, "join_types", "LEFT")
