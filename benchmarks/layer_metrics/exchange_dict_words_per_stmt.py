"""Words of string dictionaries a statement's producers wrote into the
exchange's frames: the sum of `dict_words` over the `serialize` spans in
the window (a frame carries each string column's dictionary whole; a span
the window cuts counts by the share of it inside), over the statements
attempted. 0.0 where `serialize` spans ran and wrote no dictionary;
nothing where no `serialize` span carries the attribute."""

import span_reduce


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None:
        return None
    if not any(s.name == "serialize" and "dict_words" in s.stats
               and s.start_s <= w.hi and s.end_s >= w.lo for s in w.spans):
        return None
    return span_reduce.attribute_sum(w.spans, "serialize", "dict_words",
                                     w.lo, w.hi) / w.statements
