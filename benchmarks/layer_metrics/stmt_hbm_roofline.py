"""Share of the HBM roofline that the statements' device programs reach:
the least time the chips could take to read once every column the
statements reference (rows x column bytes over the peak bandwidth, over
all the chips of the cell) against the time the device was busy for them.
Bound by bytes, not by operations: these are scans, joins and sums. No
device time read means no share, never 0."""


def read(ctx):
    if ctx["device"] is None or ctx["device"]["busy_max_s"] <= 0 \
            or ctx["floor_s"] is None:
        return None
    return 100.0 * ctx["floor_s"] / ctx["device"]["busy_max_s"]
