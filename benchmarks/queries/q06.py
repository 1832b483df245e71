"""Plain reference of TPC-H Q6: four filters in numpy and one sum.

The discount band is decided in whole hundredths, which is what the
source's DECIMAL(15,2) means: `l_discount` is a double that stands for a
number of hundredths, and DISCOUNT - 0.01 .. DISCOUNT + 0.01 is the band
of the three whole hundredths around DISCOUNT's. A reference that
compared doubles with `DISCOUNT - 0.01` cast to a double would repeat the
program's cast and agree with its fault. Dates are day numbers.
`dtype` is the precision of the revenue arithmetic (float32 for the
control)."""

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def reference(tables, params, dtype=np.float64):
    first = datetime.date.fromisoformat(params["DATE"])
    lo = (first - _EPOCH).days
    hi = (first.replace(year=first.year + 1) - _EPOCH).days
    ship = tables.column("lineitem", "l_shipdate")
    disc = tables.column("lineitem", "l_discount")
    hundredths = np.rint(disc * 100).astype(np.int64)
    mid = round(100 * float(params["DISCOUNT"]))
    keep = ((ship >= lo) & (ship < hi)
            & (hundredths >= mid - 1) & (hundredths <= mid + 1)
            & (tables.column("lineitem", "l_quantity")
               < int(params["QUANTITY"])))
    price = tables.column("lineitem", "l_extendedprice")[keep]
    revenue = np.sum(price.astype(dtype) * disc[keep].astype(dtype),
                     dtype=dtype)
    # SQL's sum over no row is NULL
    return [[float(revenue) if keep.any() else None]]
