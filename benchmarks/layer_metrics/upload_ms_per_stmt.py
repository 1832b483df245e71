"""Milliseconds of host-to-device upload a statement: the wall the
`upload` spans cover (a scan's splits concatenated and put on the device;
pulled exchange pages fused and put there), over the statements
attempted."""

import span_reduce


def read(ctx):
    return span_reduce.ms_per_stmt(ctx, "upload")
