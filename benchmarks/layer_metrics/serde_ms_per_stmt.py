"""Milliseconds of page (de)serialization a statement: the wall the
`download` (an output page device to host), `serialize` (partition, wire
blocks, compression, buffer) and `deserialize` (wire frames to pages)
spans cover, union over threads, over the statements attempted."""

import span_reduce


def read(ctx):
    return span_reduce.ms_per_stmt(ctx, "download", "serialize",
                                   "deserialize")
