"""Share of the traced window in which no operation ran on the device
(mean over the chips of the cell)."""


def read(ctx):
    if ctx["device"] is None:
        return None
    return 100.0 * (1.0 - ctx["device"]["busy_s"] / ctx["device"]["window_s"])
