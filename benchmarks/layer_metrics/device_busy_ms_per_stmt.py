"""Milliseconds in which an operation ran on the device (union of the
trace's operation intervals; on several chips the busiest one), over the
statements of the traced window."""


def read(ctx):
    if ctx["device"] is None or not ctx["records"]:
        return None
    return 1e3 * ctx["device"]["busy_max_s"] / len(ctx["records"])
