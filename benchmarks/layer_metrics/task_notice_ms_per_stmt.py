"""Milliseconds a statement's coordinator learns late that its tasks are
done: the wall the program's `task_notice` markers cover inside the
traced window (each from the worker's terminal transition to the answer
of the watcher's status poll that carried it), over the statements
attempted. Nothing where the workers do not say when their state
changed."""

import span_reduce


def read(ctx):
    return span_reduce.ms_per_stmt(ctx, "task_notice")
