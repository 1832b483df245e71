"""Island dispatches a statement that found no program in their worker's
cache: the `dispatch` spans that start inside the window with `first_call`
true (a Python trace, a lowering and a compile request follow each), over
the statements attempted. 0.0 where every dispatch was a hit; nothing
where the window holds no `dispatch` span that says."""

import span_reduce


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None:
        return None
    calls = [s for s in w.spans if s.name == "dispatch"
             and "first_call" in s.stats and w.lo <= s.start_s <= w.hi]
    if not calls:
        return None
    return sum(1 for s in calls if int(s.stats["first_call"])) \
        / w.statements
