"""Milliseconds of the statement protocol's own work a statement: the
wall the program's `statement_post` (POST /v1/statement from the
request's arrival through submit to the first QueryResults) and
`statement_results` (a nextUri GET from the later of its arrival and the
statement's end to its response: the loop's wake-up, `results_json`, the
JSON) markers cover inside the traced window, over the statements
attempted. Nothing where the program records neither."""

import span_reduce


def read(ctx):
    return span_reduce.ms_per_stmt(ctx, "statement_post",
                                   "statement_results")
