"""Plain reference of TPC-H Q3: filters in numpy, two pandas merges, a
groupby and the top ten. `dtype` is the precision of the revenue
arithmetic (float32 for the control)."""

import datetime

import numpy as np
import pandas as pd

_EPOCH = datetime.date(1970, 1, 1)


def reference(tables, params, dtype=np.float64):
    cutoff = (datetime.date.fromisoformat(params["DATE"]) - _EPOCH).days
    words = tables.words("customer", "c_mktsegment")
    seg = [i for i, w in enumerate(words) if w == params["SEGMENT"]]
    ckeep = np.isin(tables.column("customer", "c_mktsegment"), seg)
    c = pd.DataFrame(
        {"c_custkey": tables.column("customer", "c_custkey")[ckeep]})
    okeep = tables.column("orders", "o_orderdate") < cutoff
    o = pd.DataFrame({k: tables.column("orders", k)[okeep] for k in (
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority")})
    lkeep = tables.column("lineitem", "l_shipdate") > cutoff
    price = tables.column("lineitem", "l_extendedprice")[lkeep]
    disc = tables.column("lineitem", "l_discount")[lkeep]
    li = pd.DataFrame({
        "l_orderkey": tables.column("lineitem", "l_orderkey")[lkeep],
        "revenue": price.astype(dtype) * (dtype(1) - disc.astype(dtype))})
    j = (li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
         .merge(c, left_on="o_custkey", right_on="c_custkey"))
    # sum each group in `dtype` itself: pandas would widen a float32 sum
    j = j.sort_values(["l_orderkey"], kind="stable")
    keys = j[["l_orderkey", "o_orderdate", "o_shippriority"]].to_numpy()
    rev = j["revenue"].to_numpy()
    if not len(j):
        return []
    starts = np.flatnonzero(np.r_[True, (keys[1:] != keys[:-1]).any(1)])
    g = pd.DataFrame(keys[starts], columns=["l_orderkey", "o_orderdate",
                                            "o_shippriority"])
    g["revenue"] = np.add.reduceat(rev, starts, dtype=dtype)
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(10)
    # the statement protocol delivers a DATE as days since 1970-01-01
    return [[int(r.l_orderkey), float(r.revenue), int(r.o_orderdate),
             int(r.o_shippriority)] for r in g.itertuples()]
