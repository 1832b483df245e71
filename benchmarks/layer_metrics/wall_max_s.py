"""The slowest statement of the window, POST to last row, on the client's
clock: where a cell completes too few statements for a tail."""


def read(ctx):
    walls = [r["t_done"] - r["t_post"] for r in ctx["records"] if r["ok"]]
    return max(walls) if walls else None
