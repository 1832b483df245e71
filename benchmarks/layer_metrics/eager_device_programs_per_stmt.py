"""Executions on the device, inside the traced window, of programs that
are not the executor's (`jit_presto_*`): `jit_add`,
`jit_convert_element_type`, ... scalar work that planning and the host
path run eagerly, each a launch. From the modules line of the busiest
device, over the statements attempted; nothing where the trace has no
such line."""

import span_reduce


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None or not w.modules:
        return None
    return span_reduce.eager_executions(w.modules, w.lo, w.hi) / w.statements
