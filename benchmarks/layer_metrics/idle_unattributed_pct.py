"""Share of the device's idle time inside statements that has no owner:
idle seconds of the busiest device, in the traced window, while a
statement was in flight and neither a compile nor a leaf span of the
program covered the time (`span_reduce.attribute_idle`), over all idle
seconds inside statements. Container spans own nothing. 0 to 100 by
construction; nothing where the device was never idle in a statement."""

import span_reduce


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None:
        return None
    owners = span_reduce.attribute_idle(
        span_reduce.idle_of(w), w.compiling, w.spans, w.in_statement)
    inside = sum(owners.values()) - owners.get("between_statements", 0.0)
    if inside <= 0:
        return None
    return 100.0 * owners["unattributed_in_statement"] / inside
