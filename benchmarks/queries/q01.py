"""Plain reference of TPC-H Q1: one filter in numpy, the rows of each
pair of words summed group by group.

The cut-off is a day number, 1998-12-01 less DELTA days; a group is a
pair of words (`l_returnflag`, `l_linestatus`) read through the two
dictionaries, never a pair of codes; the rows come ordered by the two
words. `dtype` is the precision of the products, the sums and the
averages (float32 for the control); `count_order` is a count and exact in
either."""

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)
_LAST_SHIPDATE = datetime.date(1998, 12, 1)


def reference(tables, params, dtype=np.float64):
    cutoff = (_LAST_SHIPDATE - datetime.timedelta(int(params["DELTA"]))
              - _EPOCH).days
    keep = tables.column("lineitem", "l_shipdate") <= cutoff

    def kept(col):
        return tables.column("lineitem", col)[keep]

    qty, price, disc, tax = (kept(c).astype(dtype) for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    disc_price = price * (dtype(1) - disc)
    charge = disc_price * (dtype(1) + tax)
    flag_codes, status_codes = kept("l_returnflag"), kept("l_linestatus")
    flags = tables.words("lineitem", "l_returnflag")
    statuses = tables.words("lineitem", "l_linestatus")
    rows = []
    status_seen = np.unique(status_codes)
    for f in np.unique(flag_codes):
        for s in status_seen:
            group = (flag_codes == f) & (status_codes == s)
            n = int(group.sum())
            if not n:
                continue
            sums = [np.sum(v[group], dtype=dtype)
                    for v in (qty, price, disc_price, charge, disc)]
            rows.append([str(flags[int(f)]), str(statuses[int(s)])]
                        + [float(v) for v in sums[:4]]
                        + [float(sums[i] / dtype(n)) for i in (0, 1, 4)]
                        + [n])
    return sorted(rows, key=lambda r: (r[0], r[1]))
