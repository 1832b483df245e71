"""Device milliseconds a statement inside the programs that scan a table
and aggregate it in one: the modules (busiest device, traced window)
whose `dispatch` span lists both `TableScan` and `Aggregation` among its
`operators`, over the statements attempted. A program counts whole, with
the filter and the projections fused between the two."""

import dispatch_attrs
import span_reduce


def read(ctx):
    w = span_reduce.window(ctx)
    if w is None:
        return None
    chosen = (dispatch_attrs.programs_with(w.spans, "operators", "TableScan")
              & dispatch_attrs.programs_with(w.spans, "operators",
                                             "Aggregation"))
    seconds = sum(s for name, (s, _n) in span_reduce.module_seconds(
        w.modules, w.lo, w.hi).items() if name in chosen)
    return 1e3 * seconds / w.statements if seconds > 0 else None
