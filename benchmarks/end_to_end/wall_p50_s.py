"""Median wall of a statement, POST to last row on the client's clock,
over all the statements of the window that came back with rows. Nothing
came back: nothing to read."""

import stats


def read(ctx):
    walls = stats.walls(ctx["records"])
    return stats.percentile(walls, 50) if walls else None
