"""Megabytes (1e6 bytes) that crossed the exchange inside the window,
HTTP pages (`exchange_counters()["bytes"]`) plus ICI collectives
(`mesh_tier.ici_bytes_total()`), over the statements attempted. Left out
in a cell whose statements exchange nothing worth the name."""


def read(ctx):
    if not ctx["records"] or ctx["exchange_bytes"] <= 0:
        return None
    return ctx["exchange_bytes"] / 1e6 / len(ctx["records"])
