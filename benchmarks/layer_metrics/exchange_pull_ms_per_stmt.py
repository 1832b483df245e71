"""Milliseconds a statement's fetch threads spend pulling pages from
upstream tasks: the wall the `exchange_pull` spans cover, one for each GET
that landed data (an empty long poll is the consumer's `exchange_wait`, a
container, and is not counted; the decode is `serde_ms_per_stmt`'s and the
fuse onto the device `upload_ms_per_stmt`'s), over the statements
attempted. The time beside `exchange_mb_per_stmt`'s bytes."""

import span_reduce


def read(ctx):
    return span_reduce.ms_per_stmt(ctx, "exchange_pull")
