"""Megabytes (1e6 bytes) put on the device a statement: the `bytes` of the
`upload` spans inside the traced window (pages at their capacity, values
and null lanes), over the statements attempted."""

import span_reduce


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None:
        return None
    nbytes = span_reduce.attribute_sum(w.spans, "upload", "bytes",
                                       w.lo, w.hi)
    return nbytes / 1e6 / w.statements if nbytes > 0 else None
