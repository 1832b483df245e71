"""Statements an hour (TPC-H's throughput form): 3600 x the statements
that came back and were right, over the true length of the window, which
ends when the last statement in flight is back. A stalled statement
lengthens the divisor; a wrong one is not counted."""

import stats


def read(ctx):
    return stats.per_hour(ctx["statements_right"], ctx["window_s"])
