"""HTTP round trips (the POST and every nextUri poll) a statement, from
the benchmark's own client."""


def read(ctx):
    done = [r for r in ctx["records"] if r["ok"]]
    if not done:
        return None
    return sum(r["trips"] for r in done) / len(done)
