"""Literals a statement's programs took as inputs instead of holding
them: the sum of `params` over the `dispatch` spans that start inside the
window (each says how many lifted literals that call handed its island),
over the statements attempted. Nothing where no `dispatch` span carries
`params`: a program that bakes its literals has no such attribute."""

import span_reduce


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None:
        return None
    handed = [int(s.stats["params"]) for s in w.spans
              if s.name == "dispatch" and "params" in s.stats
              and w.lo <= s.start_s <= w.hi]
    if not handed:
        return None
    return sum(handed) / w.statements
