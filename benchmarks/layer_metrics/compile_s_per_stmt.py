"""Seconds of backend compile inside the window, summed over the threads
that compiled (so it can exceed the wall), over the statements attempted.
Left out where nothing compiled: a time, not a count."""


def read(ctx):
    if not ctx["records"] or ctx["compile"]["seconds"] <= 0:
        return None
    return ctx["compile"]["seconds"] / len(ctx["records"])
