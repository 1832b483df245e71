"""Plain reference of TPC-H Q1 at a scale where a filter a statement is too
slow: one pass over lineitem to sums by (flag, status, ship day), then
each DELTA adds the days up to its cut-off.

The pass sorts the rows, stably, by the two flags' codes and the ship
day, and `np.add.reduceat` sums each run of equal keys in `dtype`; the
sums are kept on `tables`, one set a `dtype`. A statement's cut-off is a
day number, 1998-12-01 less DELTA days, and a group's sums are the sums
of its days up to it, in `dtype` again: float32 for the control, which
so stays a float32 sum. `count_order` is a count and exact in either. A
group is a pair of words (`l_returnflag`, `l_linestatus`) read through
the two dictionaries, never a pair of codes; the rows come ordered by
the two words, as `queries/q01.py` gives them."""

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)
_LAST_SHIPDATE = datetime.date(1998, 12, 1)


def _day_sums(tables, dtype):
    """{(flag code, status code): (days, [five sums a day], rows a day)},
    the days ascending."""
    kept = tables.__dict__.setdefault("_q01_day_sums", {})
    key = np.dtype(dtype).name
    if key in kept:
        return kept[key]

    def col(c):
        return tables.column("lineitem", c)

    flag, status, day = (col(c).astype(np.int64) for c in (
        "l_returnflag", "l_linestatus", "l_shipdate"))
    first = day.min()
    n_status, n_days = int(status.max()) + 1, int(day.max() - first) + 1
    cell = (flag * n_status + status) * n_days + (day - first)
    order = np.argsort(cell, kind="stable")
    cell = cell[order]
    starts = np.flatnonzero(np.r_[True, cell[1:] != cell[:-1]])
    counts = np.diff(np.r_[starts, len(cell)])
    cell = cell[starts]
    del flag, status, day

    qty, price, disc, tax = (col(c).astype(dtype) for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    disc_price = price * (dtype(1) - disc)
    charge = disc_price * (dtype(1) + tax)
    sums = [np.add.reduceat(v[order], starts, dtype=dtype)
            for v in (qty, price, disc_price, charge, disc)]

    out = {}
    group = cell // n_days
    for g in np.unique(group):
        at = group == g
        out[(int(g // n_status), int(g % n_status))] = (
            cell[at] % n_days + first, [s[at] for s in sums], counts[at])
    kept[key] = out
    return out


def reference(tables, params, dtype=np.float64):
    cutoff = (_LAST_SHIPDATE - datetime.timedelta(int(params["DELTA"]))
              - _EPOCH).days
    flags = tables.words("lineitem", "l_returnflag")
    statuses = tables.words("lineitem", "l_linestatus")
    rows = []
    for (f, s), (days, sums, counts) in _day_sums(tables, dtype).items():
        upto = days <= cutoff
        n = int(counts[upto].sum())
        if not n:
            continue
        total = [np.sum(v[upto], dtype=dtype) for v in sums]
        rows.append([str(flags[f]), str(statuses[s])]
                    + [float(v) for v in total[:4]]
                    + [float(total[i] / dtype(n)) for i in (0, 1, 4)]
                    + [n])
    return sorted(rows, key=lambda r: (r[0], r[1]))
