"""Programs the XLA compiler really built inside the window (backend
compile requests less persistent-cache hits, from jax.monitoring) over
the statements the window attempted."""


def read(ctx):
    if not ctx["records"]:
        return None
    return ctx["compile"]["compiled"] / len(ctx["records"])
